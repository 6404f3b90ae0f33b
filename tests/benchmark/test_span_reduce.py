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


def test_reduce_sums_every_numeric_stat_and_lists_a_span_no_list_names():
    spans = [
        Span("plane.take", LOOP, 0, 1 * MS, {
            "step": 4, "frames": 10, "ring_wait_us": 700, "users": 1024,
            "drained": 3}),
        Span("plane.egress", LOOP, 5 * MS, 9 * MS, {
            "step": 4, "deliveries": 40, "inline": 7, "queued": 2,
            "batched": 6}),
        Span("plane.take", LOOP, 10 * MS, 12 * MS, {
            "step": 5, "frames": 20, "ring_wait_us": 300, "users": 1024,
            "drained": 0}),
        # an older commit's egress: no ``batched``; a stat that is no
        # number and one that is a bool are not summed
        Span("plane.egress", LOOP, 15 * MS, 16 * MS, {
            "step": 5, "deliveries": 60, "inline": 1, "queued": 9,
            "lane": "wide", "full": True}),
        # a span a later PR adds under a dotted lower-case name
        Span("links.forward", LOOP, 20 * MS, 20.5 * MS, {"peers": 3}),
        Span("links.forward", WORKER, 21 * MS, 22.5 * MS, {"peers": 2.5}),
    ]
    out = span_reduce.reduce(spans)
    assert out["stats"] == {
        "plane.take": {"frames": 30, "ring_wait_us": 1000, "users": 2048,
                       "drained": 3},
        "plane.egress": {"deliveries": 100, "inline": 8, "queued": 11,
                         "batched": 6},
        "links.forward": {"peers": 5.5},
    }
    assert list(out["spans"]) == ["plane.take", "plane.egress",
                                  "links.forward"]
    assert out["spans"]["links.forward"] == pytest.approx(
        {"count": 2, "total_ms": 2.0, "median_ms": 1.0})
    # what was there reads as it read
    assert out["steps"] == 2 and out["egress"]["deliveries"] == 100
    run = SimpleNamespace(window=SimpleNamespace(spans=out))
    assert span_reduce.stat_sum(run, "plane.egress", "batched") == 6
    assert span_reduce.stat_sum(run, "links.forward", "peers") == 5.5
    assert span_reduce.stat_sum(run, "links.forward", "nope") is None
    assert span_reduce.stat_sum(run, "links.nope", "peers") is None
    # which names are the program's: dotted and lower-case, each part
    # opening with a letter; never an XLA op or a runtime event
    for name, mine in (("links.forward", True), ("plane.take", True),
                       ("a.b_c.d2", True), ("fusion.1", False),
                       ("np.asarray(jax.Array)", False), ("copy", False),
                       ("PjitFunction(step)", False), ("plane.", False),
                       ("Plane.take", False), ("dynamic-slice.3", False)):
        assert bool(span_reduce.SPAN_NAME.match(name)) is mine, name


@pytest.mark.parametrize("name,want", [
    ("egress_batched_share", 6 / 10), ("egress_inline_share", 8 / 10)])
def test_the_two_readers_of_the_pass_throughs(name, want):
    reader = manifest.layer_metric(REPO, name)
    stats = {"plane.egress": {"deliveries": 50, "inline": 8, "queued": 2,
                              "batched": 6}}
    marks = {"start": {"egress_inline": 100, "egress_queued": 40},
             "end": {"egress_inline": 108, "egress_queued": 42}}

    def read(stats, marks):
        return reader.read(SimpleNamespace(window=SimpleNamespace(
            spans={"stats": stats}, counters=marks)))

    assert read(stats, marks) == pytest.approx(want)
    # nothing to read is nothing, never 0: the mesh group's span has no
    # ``batched``, an older launcher passes no such counter, a bypassed
    # cell hands nothing off, an untraced mark is missing
    if name == "egress_batched_share":
        assert read({"plane.egress": {"inline": 8, "queued": 2}}, marks) is None
        assert read({}, marks) is None
        assert read({"plane.egress": {"inline": 0, "queued": 0,
                                      "batched": 0}}, marks) is None
        # none batched of some handed off is a reading
        assert read({"plane.egress": {"inline": 2, "queued": 8,
                                      "batched": 0}}, marks) == 0
    else:
        assert read(stats, {"start": {"egress_queued": 1},
                            "end": {"egress_queued": 2}}) is None
        assert read(stats, {"end": marks["end"]}) is None
        assert read(stats, {"start": marks["end"], "end": marks["end"]}) is None
        assert read(stats, {"start": {"egress_inline": 5, "egress_queued": 5},
                            "end": {"egress_inline": 5,
                                    "egress_queued": 9}}) == 0


def test_the_recorded_trace_of_an_older_program_still_reads_as_nothing():
    """``fixtures/global_steady_3s.xplane.pb`` was recorded before the
    program had spans: the wider rule for a span's name must find none
    among the runtime's 3,658 host events, and the nine readers read
    what they read of it before, which is nothing."""
    path = os.path.join(REPO, "benchmark", "fixtures",
                        "global_steady_3s.xplane.pb")
    assert span_reduce.load(path) == []
    run = SimpleNamespace(window=SimpleNamespace(
        spans=span_reduce.reduce(span_reduce.load(path))))
    for name in sorted(SPAN_METRICS | {"egress_batched_share"}):
        assert manifest.layer_metric(REPO, name).read(run) is None, name


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
    # the nine of PR 24 among whatever later PRs add as files and entries
    assert SPAN_METRICS <= set(entries)
    assert all(entries[name]["better"] == "lower" for name in SPAN_METRICS)
    # the two steady cells, one of which records its p50 per layer and has
    # the step's metrics entered apart (``<metric>.<tag>``, PR 32): the
    # same readers, moving what that cell still judges
    for cell in ("broker1-1k.global-steady", "broker1-5k.global5k-steady"):
        steady = manifest.Cell(manifest.load(REPO), cell)
        reported = {manifest.base_name(m["name"]) for m in steady.per_layer}
        assert set(STEP_METRICS) <= reported, cell
        assert "sat_step_wall_ms" not in reported
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
    assert "step_wall_ms.global1k" in line["metrics"]  # as BENCHMARK.json
    metrics = {manifest.base_name(k): v["value"]
               for k, v in line["metrics"].items()}
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
    # every numeric stat the program put on a span, summed; ``step`` is
    # an index and is left out
    assert set(spans["stats"]) == set(spans["spans"])
    assert {"inline", "queued", "deliveries"} <= set(
        spans["stats"]["plane.egress"])
    assert {"frames", "ring_wait_us", "users"} <= set(
        spans["stats"]["plane.take"])
    assert not any("step" in row for row in spans["stats"].values())
    # the two readers of the pass-throughs (PR 32) find their numbers. The
    # one over the window's counters has nothing to read where the plane
    # delivered nothing between the window's marks (at 16 users every frame
    # of the 3 s may meet an idle plane and be host-routed: 6 runs of 18,
    # PR 33): the run's own counters have to say so, or the key is there
    said = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("[bench] counters: ")]
    assert len(said) == 1
    marks = json.loads(said[0].split(": ", 1)[1])
    if marks["end"]["messages_routed"] == marks["start"]["messages_routed"]:
        assert "egress_inline_share" not in metrics
        assert 0 <= metrics["egress_batched_share"] <= 1
    else:
        assert 0 <= metrics["egress_inline_share"] <= 1
        assert 0 <= metrics["egress_batched_share"] <= metrics[
            "egress_inline_share"]
    inline = spans["stats"]["plane.egress"]["inline"]
    queued = spans["stats"]["plane.egress"]["queued"]
    assert metrics["egress_batched_share"] == pytest.approx(
        spans["stats"]["plane.egress"]["batched"] / (inline + queued))
    # and the reduction that was there names gaps after the bare spans
    assert any(label.split(": ")[1].startswith("plane.")
               and "#" not in label
               for label, _s in line["breakdown"]["idle_gaps"])
