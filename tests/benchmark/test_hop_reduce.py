"""The loop's side of the window (ISSUE 37): ``benchmark/hop_reduce.py``
on hand-made spans with known covers, the readers of the program's new
counters on marks made by hand, and the manifest with their entries."""

import os
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import hop_reduce, manifest, window_counters  # noqa: E402
from benchmark.span_reduce import Span  # noqa: E402

MS = 1e6  # ns
LOOP, WORKER, OTHER = 0, 1, 2  # thread lines
SAT = ["broker1-1k.fanout4-sat", "mesh4-1k.cross-sat",
       "hostlinks4-1k.cross-sat"]
PUMP = ("parked", "gate", "drain", "take", "worker", "egress")


def _step(t0, step, hop1, work, hop2, stat=True):
    """A step from ``t0`` ms on: a 1 ms take, ``hop1`` ms, two worker
    spans of ``work`` ms in all, ``hop2`` ms, a 2 ms egress."""
    stats = {"step": step} if stat else {}
    t = t0
    spans = [Span("plane.take", LOOP, t * MS, (t + 1) * MS, dict(stats))]
    t += 1 + hop1
    spans.append(Span("plane.h2d", WORKER, t * MS, (t + work / 2) * MS,
                      dict(stats)))
    t += work / 2
    spans.append(Span("plane.encode", WORKER, t * MS, (t + work / 2) * MS,
                      dict(stats)))
    t += work / 2 + hop2
    spans.append(Span("plane.egress", LOOP, t * MS, (t + 2) * MS,
                      dict(stats)))
    return spans


@pytest.mark.parametrize("stat", [True, False],
                         ids=["joined_by_step", "joined_by_time"])
def test_hops_and_what_the_loop_ran_in_them(stat):
    # step 7: take 0..1, hop 1 1..3, worker 3..13, hop 2 13..19, egress
    # 19..21; step 8: take 100..101, hop 1 101..105, worker 105..115,
    # hop 2 115..116, egress 116..118
    spans = (
        _step(0, 7, hop1=2, work=10, hop2=6, stat=stat)
        + _step(100, 8, hop1=4, work=10, hop2=1, stat=stat)
        # a step the trace ended inside: no egress, no hop
        + _step(200, 9, hop1=1, work=2, hop2=1, stat=stat)[:-1]
        + [
            # inside step 7's hop 1 for 1.5 ms of its 2
            Span("ingress.scan", LOOP, 1.25 * MS, 2.75 * MS, {"frames": 9}),
            # across the worker phase and 2 ms into hop 2 (13..15)
            Span("ingress.stage", LOOP, 12 * MS, 15 * MS, {"frames": 9}),
            # hop 2 again, wholly (16..18), and once outside any hop
            Span("ingress.scan", LOOP, 16 * MS, 18 * MS, {"frames": 3}),
            Span("ingress.scan", LOOP, 50 * MS, 55 * MS, {"frames": 3}),
            # a later PR's span on the loop thread: 0.5 ms of step 8's
            # hop 2, the rest under its egress's start (flat: it ends)
            Span("links.scan", LOOP, 115.5 * MS, 116 * MS, {"frames": 1}),
            # the same name on another thread does not count: the
            # continuation does not wait behind another thread's span
            Span("links.scan", OTHER, 101 * MS, 105 * MS, {"frames": 1}),
        ])
    found = hop_reduce.hops(spans)
    assert sorted(found) == [((1 * MS, 3 * MS), (13 * MS, 19 * MS)),
                             ((101 * MS, 105 * MS), (115 * MS, 116 * MS))]
    out = hop_reduce.reduce(spans)
    assert out["steps"] == 2
    assert out["hop1_ms"] == pytest.approx(2 + 4)
    assert out["hop2_ms"] == pytest.approx(6 + 1)
    assert out["hop_ms"] == pytest.approx(13)
    assert out["by_span_ms"] == pytest.approx({
        "ingress.scan": 1.5 + 2, "ingress.stage": 2, "links.scan": 0.5})
    assert out["unnamed_ms"] == pytest.approx(13 - 6)
    run = SimpleNamespace(window=SimpleNamespace(hops=out))
    reader = manifest.layer_metric(REPO, "sat_hop_loop_busy_share")
    assert reader.read(run) == pytest.approx(6 / 13)


def test_a_trace_without_a_whole_step_has_no_hops(capsys):
    assert hop_reduce.reduce([]) is None
    lone = _step(0, 3, hop1=1, work=2, hop2=1)[:-1]
    assert hop_reduce.reduce(lone) is None
    # a step without worker spans (nothing between take and egress that
    # the trace holds) has no hops either
    bare = [s for s in _step(0, 3, hop1=1, work=2, hop2=1)
            if s.thread == LOOP]
    assert hop_reduce.reduce(bare) is None
    reader = manifest.layer_metric(REPO, "sat_hop_loop_busy_share")
    # an untraced run, an older launcher: the reduction runs once, says
    # so once, and the reader leaves its metric out
    run = SimpleNamespace(window=SimpleNamespace(traced={"file": None}))
    assert reader.read(run) is None and reader.read(run) is None
    assert capsys.readouterr().out.count("[bench] hops: null") == 1


def _run(start: dict, end: dict):
    return SimpleNamespace(window=SimpleNamespace(
        counters={"before": {}, "start": start, "end": end}))


# what each reader makes of the marks below, and the keys it needs
START = {"steps": 100, "frames_staged": 1_000, "stage_full_frames": 10,
         "pump_parked_us": 5_000, "pump_gate_us": 1_000,
         "pump_drain_us": 2_000, "pump_take_us": 3_000,
         "pump_worker_us": 50_000, "pump_egress_us": 9_000,
         "worker_busy_us": 30_000,
         "writer_dequeues": 40, "writer_wait_us": 8_000,
         "writer_writes": 20, "writer_write_us": 900,
         "loop_lag_us": 700, "loop_lag_samples": 8}
END = {"steps": 150, "frames_staged": 3_000, "stage_full_frames": 510,
       "pump_parked_us": 105_000, "pump_gate_us": 51_000,
       "pump_drain_us": 52_000, "pump_take_us": 103_000,
       "pump_worker_us": 550_000, "pump_egress_us": 209_000,
       "worker_busy_us": 430_000,
       "writer_dequeues": 240, "writer_wait_us": 1_008_000,
       "writer_writes": 120, "writer_write_us": 6_900,
       "loop_lag_us": 240_700, "loop_lag_samples": 88}
READERS = {
    "pump_parked_share": (100 / 1_000, [f"pump_{s}_us" for s in PUMP]),
    "sat_step_hop_ms": ((500 - 400) / 50,
                        ["pump_worker_us", "worker_busy_us", "steps"]),
    "step_hop_ms": ((500 - 400) / 50,
                    ["pump_worker_us", "worker_busy_us", "steps"]),
    "ring_full_share": (500 / 2_000, ["stage_full_frames", "frames_staged"]),
    "writer_wait_ms": (1_000 / 200, ["writer_wait_us", "writer_dequeues"]),
    "writer_us_per_write": (6_000 / 100, ["writer_write_us",
                                          "writer_writes"]),
    "loop_lag_ms": (240 / 80, ["loop_lag_us", "loop_lag_samples"]),
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_counter_reader_on_marks_made_by_hand(name):
    want, keys = READERS[name]
    reader = manifest.layer_metric(REPO, name)
    assert reader.SOURCE == "program_counter"
    assert reader.read(_run(START, END)) == pytest.approx(want)
    # only its own keys are asked for
    assert reader.read(_run({k: START[k] for k in keys},
                            {k: END[k] for k in keys})) \
        == pytest.approx(want)
    for key in keys:
        # an older commit lacks the key at either mark; a process without
        # a sampler says None; an untraced run has no ``start``
        for broken in (_run({k: v for k, v in START.items() if k != key},
                            END),
                       _run(START, {k: v for k, v in END.items()
                                    if k != key}),
                       _run({**START, key: None}, {**END, key: None})):
            assert reader.read(broken) is None, key
    assert reader.read(SimpleNamespace(window=SimpleNamespace(
        counters={"before": START, "end": END}))) is None
    # nothing moved: no divisor, no reading
    assert reader.read(_run(END, END)) is None


def test_window_counters_differences_and_ratios():
    run = _run(START, END)
    assert window_counters.moved(run, "steps", "frames_staged") == {
        "steps": 50, "frames_staged": 2_000}
    assert window_counters.moved(run, "steps", "nope") is None
    assert window_counters.moved(run, "steps", first="before") is None
    assert window_counters.ratio(run, "frames_staged", "steps") == 40
    assert window_counters.ratio(run, "steps", "writer_writes",
                                 scale=2.0) == 1.0
    # a numerator that stood still is a reading of 0, a divisor that did
    # is none
    still = _run(START, {**END, "stage_full_frames": 10})
    assert window_counters.ratio(still, "stage_full_frames",
                                 "frames_staged") == 0
    assert window_counters.ratio(still, "frames_staged",
                                 "stage_full_frames") is None


def test_the_manifest_holds_the_new_entries_and_lints_clean():
    assert manifest.lint(REPO) == []
    loaded = manifest.load(REPO)
    entries = {m["name"]: m for m in loaded["per_layer"]}
    for name in ("pump_parked_share", "sat_step_hop_ms",
                 "sat_hop_loop_busy_share", "ring_full_share"):
        assert entries[name]["workloads"] == SAT, name
    assert entries["sat_hop_loop_busy_share"]["source"] == "program_span"
    assert entries["ring_full_share"]["layer"] == "stage_pack"
    # one reader, entered apart for the cell whose p50 is not judged
    assert manifest.layer_metric_path("step_hop_ms.global1k") == \
        manifest.layer_metric_path("step_hop_ms")
    assert entries["step_hop_ms"]["workloads"] == [
        "broker1-5k.global5k-steady"]
    assert entries["step_hop_ms.global1k"]["workloads"] == [
        "broker1-1k.global-steady"]
    assert entries["step_hop_ms.global1k"]["moves"] == \
        "broker_cpu_us_per_delivery"
    # the writers' two share their cells; the sampler runs only where the
    # routing process serves a metrics endpoint, which the in-process
    # group's launcher does not
    assert entries["writer_wait_ms"]["workloads"] == \
        entries["writer_us_per_write"]["workloads"]
    assert "mesh4-1k.cross-sat" not in entries["loop_lag_ms"]["workloads"]
    launchers = {c["name"]: manifest.read_json(REPO, c["file"])["launcher"]
                 for c in loaded["configs"]}
    for w in loaded["workloads"]:
        assert (w["name"] in entries["loop_lag_ms"]["workloads"]) == \
            (launchers[w["config"]] != "mesh_inprocess"), w["name"]
    # appended: what was there stands where it stood
    names = [m["name"] for m in loaded["per_layer"]]
    assert names.index("interest_synced_s") < names.index(
        "pump_parked_share")


def test_traced_dry_run_of_a_saturated_cell_reports_the_loops_side():
    import json
    import subprocess
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "PYTHONPATH",
                        "JAX_COMPILATION_CACHE_DIR")}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "broker1-1k.fanout4-sat", "--seed", "3700000001",
         "--seconds", "3", "--trace", "1", "--test-size", "16,2,2"],
        capture_output=True, text=True, timeout=180, cwd=REPO,
        env={**env, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, proc.stdout[-3000:]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # a closed loop at capacity: the pump is never parked for long, a
    # step has hops, the ring fills, and the loop's lag is sampled
    assert 0 <= metrics["pump_parked_share"] < 0.5
    assert metrics["sat_step_hop_ms"] > 0
    assert 0 < metrics["ring_full_share"] < 1
    assert metrics["loop_lag_ms"] >= 0
    assert 0 <= metrics["sat_hop_loop_busy_share"] <= 1
    said = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("[bench] hops: ")]
    assert len(said) == 1
    hops = json.loads(said[0].split(": ", 1)[1])
    assert hops["steps"] >= 2 and hops["hop_ms"] == pytest.approx(
        hops["hop1_ms"] + hops["hop2_ms"])
    assert hops["unnamed_ms"] == pytest.approx(
        hops["hop_ms"] - sum(hops["by_span_ms"].values()))
    assert set(hops["by_span_ms"]) <= {"ingress.scan", "ingress.stage"}
    # the whole account is in the launcher's pass-through, cumulative
    said = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("[bench] counters at the end, every key: ")]
    final = json.loads(said[0].split(": ", 1)[1])
    assert all(final[f"pump_{state}_us"] >= 0 for state in PUMP)
    assert 0 < final["worker_busy_us"] <= final["pump_worker_us"]
    assert final["stage_full_results"] >= final["stage_full_frames"] > 0
    assert final["profiler_ticks"] > 0 and final["profiler_tick_us"] > 0
    assert final["loop_lag_samples"] > 0
