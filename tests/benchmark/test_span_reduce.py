"""The span reduction (ISSUE 24): ``benchmark/span_reduce.py``'s step join
on hand-made events, the manifest with the nine ``program_span`` metrics,
and one traced dry run that reports the host side of the step."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest, span_reduce  # noqa: E402
from benchmark.span_reduce import Span  # noqa: E402

MS = 1e6  # ns
LOOP, WORKER = 0, 1  # thread lines
STEP_METRICS = {"step_wall_ms": "wall", "step_handoff_ms": "handoff",
                "step_h2d_ms": "h2d", "step_dispatch_ms": "dispatch",
                "step_d2h_ms": "d2h", "ring_wait_ms": "ring_wait"}
SPAN_METRICS = set(STEP_METRICS) | {
    "sat_step_wall_ms", "ingress_us_per_frame", "egress_us_per_delivery"}


def _step(t0, step, take, hop1, h2d, dispatch, between, d2h, encode, hop2,
          egress, ring_wait_us, deliveries, stat=True):
    """One step's spans from ``t0`` ms on: every length in ms; two d2h and
    two encode spans (two busy lanes), ``between`` ms of plain Python
    after the dispatch."""
    spans, t = [], t0 * MS

    def add(name, thread, ms, **stats):
        nonlocal t
        if stat:
            stats["step"] = step
        spans.append(Span(name, thread, t, t + ms * MS, stats))
        t += ms * MS

    add("plane.take", LOOP, take, frames=10, ring_wait_us=ring_wait_us)
    t += hop1 * MS
    add("plane.h2d", WORKER, h2d)
    add("plane.dispatch", WORKER, dispatch)
    t += between * MS
    for _lane in range(2):
        add("plane.d2h", WORKER, d2h / 2)
        add("plane.encode", WORKER, encode / 2)
    t += hop2 * MS
    add("plane.egress", LOOP, egress, deliveries=deliveries)
    return spans


@pytest.mark.parametrize("stat", [True, False],
                         ids=["joined_by_step", "joined_by_time"])
def test_step_join_on_two_hand_made_steps(stat):
    spans = (
        _step(0, 7, take=1, hop1=2, h2d=3, dispatch=4, between=0.5, d2h=6,
              encode=2, hop2=5, egress=9, ring_wait_us=1500,
              deliveries=100, stat=stat)
        + _step(100, 8, take=3, hop1=4, h2d=5, dispatch=6, between=1.5,
                d2h=10, encode=4, hop2=7, egress=1, ring_wait_us=500,
                deliveries=300, stat=stat)
        # a step the trace ended inside: no egress, not counted
        + _step(200, 9, take=1, hop1=1, h2d=1, dispatch=1, between=0, d2h=2,
                encode=2, hop2=1, egress=1, ring_wait_us=9,
                deliveries=0, stat=stat)[:-1]
        + [Span("ingress.scan", LOOP, 50 * MS, 50.2 * MS, {"frames": 30}),
           Span("ingress.stage", LOOP, 50.2 * MS, 50.3 * MS,
                {"frames": 30, "staged": 28}),
           Span("ingress.scan", LOOP, 112 * MS, 112.5 * MS, {"frames": 10})])
    rows = span_reduce.steps_of(spans)
    assert len(rows) == 2
    first, second = rows
    assert first == pytest.approx({
        "wall": 1 + 2 + 3 + 4 + 0.5 + 6 + 2 + 5, "handoff": 2 + 5, "h2d": 3,
        "dispatch": 4, "d2h": 6, "encode": 2, "ring_wait": 1.5})
    assert second == pytest.approx({
        "wall": 3 + 4 + 5 + 6 + 1.5 + 10 + 4 + 7, "handoff": 4 + 7, "h2d": 5,
        "dispatch": 6, "d2h": 10, "encode": 4, "ring_wait": 0.5})

    out = span_reduce.reduce(spans)
    assert out["steps"] == 2
    assert out["step_ms"] == pytest.approx(
        {k: (first[k] + second[k]) / 2 for k in first})
    assert out["spans"]["plane.d2h"]["count"] == 6
    assert out["spans"]["plane.d2h"]["total_ms"] == pytest.approx(6 + 10 + 2)
    assert out["spans"]["plane.take"]["median_ms"] == pytest.approx(1)
    assert out["ingress"] == pytest.approx({"us": 800.0, "frames": 40})
    # the incomplete step's two encode spans count: they are time spent
    assert out["egress"] == pytest.approx(
        {"us": (2 + 4 + 2 + 9 + 1) * 1e3, "deliveries": 400})
    # the loop sat in ingress.scan for 0.5 of the second step's 5 ms h2d
    # (107..112) and 6 ms dispatch (112..118): only the dispatch overlaps
    assert out["loop_busy_share_during"]["dispatch"] == \
        pytest.approx(0.5 / (4 + 6 + 1))
    assert out["loop_busy_share_during"]["h2d"] == 0

    run = SimpleNamespace(window=SimpleNamespace(spans=out))
    assert span_reduce.step_median_ms(run, "wall") == out["step_ms"]["wall"]
    assert span_reduce.us_per(run, "ingress", "frames") == \
        pytest.approx(20.0)
    assert span_reduce.us_per(run, "egress", "deliveries") == \
        pytest.approx(45.0)


def test_a_trace_without_the_programs_spans_reads_as_nothing(capsys):
    assert span_reduce.reduce([]) is None
    # an older commit's traced run: every reader leaves its metric out
    run = SimpleNamespace(window=SimpleNamespace(traced={"file": None}))
    for name in sorted(SPAN_METRICS):
        assert manifest.layer_metric(REPO, name).read(run) is None, name
    # the reduction ran once and said so once
    assert capsys.readouterr().out.count("[bench] spans: null") == 1
    # spans but no complete step, no frame, no delivery
    lone = [Span("plane.h2d", WORKER, 0.0, MS, {"step": 3})]
    run = SimpleNamespace(window=SimpleNamespace(
        spans=span_reduce.reduce(lone)))
    for name in sorted(SPAN_METRICS):
        assert manifest.layer_metric(REPO, name).read(run) is None, name


def test_the_manifest_holds_the_nine_span_metrics_and_lints_clean():
    assert manifest.lint(REPO) == []
    entries = {m["name"]: m for m in manifest.load(REPO)["per_layer"]
               if m["source"] == "program_span"}
    assert set(entries) == SPAN_METRICS
    assert all(m["better"] == "lower" for m in entries.values())
    steady = manifest.Cell(manifest.load(REPO), "broker1-1k.global-steady")
    reported = {m["name"] for m in steady.per_layer}
    assert set(STEP_METRICS) <= reported and "sat_step_wall_ms" not in reported
    for cell in ("broker1-1k.fanout4-sat", "mesh4-1k.cross-sat"):
        names = {m["name"] for m in
                 manifest.Cell(manifest.load(REPO), cell).per_layer}
        assert SPAN_METRICS & names == {
            "sat_step_wall_ms", "ingress_us_per_frame",
            "egress_us_per_delivery"}, cell
    echo = {m["name"] for m in manifest.Cell(
        manifest.load(REPO), "broker1-1k.echo-sparse").per_layer}
    assert SPAN_METRICS & echo == {"ingress_us_per_frame"}


def test_traced_dry_run_reports_the_host_side_of_the_step():
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "PYTHONPATH",
                        "JAX_COMPILATION_CACHE_DIR")}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "broker1-1k.global-steady", "--seed", "24",
         "--seconds", "3", "--trace", "1", "--test-size", "16,2,2"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**env, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, proc.stdout[-3000:]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    for name in list(STEP_METRICS) + ["ingress_us_per_frame",
                                      "egress_us_per_delivery"]:
        assert metrics[name] > 0, (name, metrics)
    assert "sat_step_wall_ms" not in metrics
    assert (metrics["step_handoff_ms"] + metrics["step_h2d_ms"]
            + metrics["step_dispatch_ms"] + metrics["step_d2h_ms"]
            <= metrics["step_wall_ms"])
    # the table PERF.md is written from
    said = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("[bench] spans: ")]
    assert len(said) == 1
    spans = json.loads(said[0].split(": ", 1)[1])
    assert spans["steps"] >= 2 and set(spans["step_ms"]) == set(
        span_reduce.PARTS)
    assert set(spans["spans"]) == set(span_reduce.LOOP + span_reduce.WORKER)
    # and the reduction that was there names gaps after the bare spans
    assert any(label.split(": ")[1].startswith("plane.")
               and "#" not in label
               for label, _s in line["breakdown"]["idle_gaps"])
