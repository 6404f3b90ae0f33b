"""Skewed topics under saturation as a dry run (ISSUE 41):
``broker1-1k.zipf-sat``, ``fanout4-sat``'s publishers and window over all
256 topics drawn Zipf 0.99 at 100 B and 1 KB, on an explicit
``JAX_PLATFORMS=cpu`` with the user count cut to 16 by the harness's
test-only argument, untraced and traced, as the cells before it. The
plan's draw is held to the arithmetic the cell was sized by, and the two
readers of the egress counters it brought are held to a run of a commit
that lacks the counters (the parent's case): they find nothing and leave
their metric out."""

import itertools
import json
import os
import subprocess
import sys
from collections import Counter
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest  # noqa: E402
from benchmark.loadgen import plan  # noqa: E402

RUN = os.path.join(REPO, "benchmark", "run.py")
CELL = "broker1-1k.zipf-sat"
UNIFORM = "broker1-1k.fanout4-sat"
NEW = ("egress_oversize_per_step", "egress_pool_fresh_share")
# what ``test_hop_reduce.py`` holds to exactly the three older ``-sat`` cells
PINNED = {"pump_parked_share", "sat_step_hop_ms", "sat_hop_loop_busy_share",
          "ring_full_share"}
ZIPF = {"zipf": 256, "s": 0.99}


def _dry_run(*args):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "PYTHONPATH",
                        "JAX_COMPILATION_CACHE_DIR")}
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seconds", "2",
         "--test-size", "16,2,2", *args],
        capture_output=True, text=True, timeout=180, cwd=REPO,
        env={**env, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, \
        proc.stdout[-3000:]
    assert all(number == limit for number, limit in line["checks"].values())
    assert line["checks"]["users_connected"] == [16, 16]
    return proc.stdout, line


def _marks(out, prefix):
    said, = [ln for ln in out.splitlines() if ln.startswith(prefix)]
    return json.loads(said.split(": ", 1)[1])


def test_the_cell_is_the_uniform_cell_with_the_topics_drawn_zipf():
    cell, uniform = manifest.find_cell(CELL), manifest.find_cell(UNIFORM)
    assert cell.workload["chips"] == 1
    assert cell.workload["config"] == "broker1-1k-zipf"
    t, u = cell.traffic, uniform.traffic
    assert t["subscriptions"] == [{"users": "all", "topic": {"mod": 256}}]
    flow, = t["flows"]
    assert flow["publishers"] == u["flows"][0]["publishers"] == 8
    assert flow["loop"] == u["flows"][0]["loop"]
    assert flow["mix"] == [
        {"share": 0.45, "kind": "broadcast", "bytes": 1000, "topic": ZIPF},
        {"share": 0.45, "kind": "broadcast", "bytes": 100, "topic": ZIPF},
        {"share": 0.1, "kind": "direct", "bytes": 256,
         "to": {"group_offset": 0}}]
    assert "YCSB" in t["why"] and "OpenMessaging" in t["why"]
    # it reports what the uniform cell reports (but the four whose lists a
    # test of the benchmark's own pins) and the two counters' readers
    assert {m["name"] for m in cell.end_to_end} == {
        m["name"] for m in uniform.end_to_end}
    reported = {m["name"] for m in cell.per_layer}
    assert ({m["name"] for m in uniform.per_layer} - PINNED) | set(NEW) \
        <= reported
    assert not PINNED & reported
    assert {"writer_wait_ms", "writer_us_per_write", "loop_lag_ms"} \
        <= reported
    entries = {m["name"]: m for m in manifest.load()["per_layer"]}
    assert entries["egress_oversize_per_step"]["workloads"] == [CELL]
    assert {UNIFORM, "broker1-1k.global-steady", CELL} <= set(
        entries["egress_pool_fresh_share"]["workloads"])
    assert manifest.lint() == []


def test_the_configuration_is_broker1_1k_with_the_skew_it_states():
    """The deployment is ``broker1-1k``'s, process for process and flag for
    flag: what it adds is the skew, stated once in its file and drawn by
    the traffic it runs."""
    cell, uniform = manifest.find_cell(CELL), manifest.find_cell(UNIFORM)
    cfg, base = cell.config, uniform.config
    added = {"skew"}
    told = {"name", "source", "source_detail", "assumed"}
    assert set(cfg) == set(base) | added
    for key in set(base) - told:
        assert cfg[key] == base[key], key
    assert base["assumed"].items() <= cfg["assumed"].items()
    assert cfg["source"] != base["source"] and "YCSB" in cfg["source"]
    skew = cfg["skew"]
    assert skew["traffic"] == cell.workload["traffic"]
    flow, = cell.traffic["flows"]
    drawn = [m for m in flow["mix"] if m["kind"] == "broadcast"]
    assert all(m["topic"] == skew["distribution"] for m in drawn)
    assert sorted(m["bytes"] for m in drawn) == skew["payload_bytes"]
    assert skew["distribution"]["zipf"] == skew["topics"] == 256


def test_one_seed_draws_topic_0_and_the_two_sizes_at_their_shares():
    cell = manifest.find_cell(CELL)
    cfg = cell.config
    layout = plan.Layout(
        cfg["users"], cfg["placement_groups"],
        cfg["client_processes"]["subscribers"],
        cfg["client_processes"]["publishers"], cell.traffic["flows"])
    flow = cell.traffic["flows"][0]
    frames = [f for p in range(flow["publishers"]) for f in itertools.islice(
        plan.frame_plan(4100000007, layout, flow, p), 12_800)]
    broadcasts = [f for f in frames if f[0] == plan.BROADCAST]
    topics = Counter(f[1] for f in broadcasts)
    weight = 1 / sum((k + 1) ** -0.99 for k in range(256))
    assert weight == pytest.approx(0.159, abs=0.0005)
    assert abs(topics[0] / len(broadcasts) - weight) < 0.01
    assert topics[0] > 1.8 * topics[1] > 0 and max(topics) <= 255
    drawn = [f for f in frames if f[0] != plan.PROBE]
    sizes = Counter(f[2] for f in drawn)
    for size in (1000, 100):
        assert abs(sizes[size] / len(drawn) - 0.45) < 0.01, size
    assert abs(sizes[256] / len(drawn) - 0.10) < 0.01


def test_untraced_dry_run_reports_the_three_end_to_end_metrics():
    out, line = _dry_run("--seed", "4100000011", "--trace", "0")
    assert line["attempted"] > 100 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {
        "delivered_per_s", "broker_cpu_us_per_delivery", "setup_s"}
    for metric in line["metrics"].values():
        assert metric["value"] > 0 and metric["unit"]
    final = _marks(out, "[bench] counters at the end, every key: ")
    assert final["users"] == 16 and final["unmirrored"] == 0
    # the program says what it took from the pool and what went over the
    # unit, through the launcher's pass-through
    assert final["egress_pool_takes"] >= final["egress_pool_fresh"] >= 1
    assert final["egress_pool_fresh_bytes"] >= final["egress_pool_fresh"] \
        << 20
    assert (final["egress_oversize_bytes"] > 65536 * final["egress_oversize"]
            if final["egress_oversize"] else
            final["egress_oversize_bytes"] == 0)


def test_traced_dry_run_reports_the_writers_inside_a_saturated_step():
    out, line = _dry_run("--seed", "4100000012", "--trace", "1")
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    for name in ("staged_share", "frames_per_step", "deliveries_per_step",
                 "sat_step_wall_ms", "sat_delivery_p99_ms", "connect_s",
                 "egress_us_per_delivery", "broker_cpu_cores"):
        assert metrics[name] > 0, (name, metrics)
    assert "delivered_per_s" not in metrics and "setup_s" not in metrics
    # over the window: the hottest topic's one subscriber at 16 users is
    # over the unit on a full step, and such a stream goes to its writer
    if metrics["egress_oversize_per_step"] > 0:
        assert metrics["egress_inline_share"] < 1
        assert metrics["writer_wait_ms"] >= 0
        assert metrics["writer_us_per_write"] > 0
    else:
        assert metrics["egress_oversize_per_step"] == 0
    assert 0 <= metrics["egress_pool_fresh_share"] <= 1
    final = _marks(out, "[bench] counters at the end, every key: ")
    assert final["egress_oversize"] <= final["egress_queued"]
    assert final["egress_pool_takes"] > 0 and final["steps"] > 0
    assert line["device"]["busy_s"] > 0


def _run(start, end):
    return SimpleNamespace(window=SimpleNamespace(
        counters={"start": start, "end": end}))


@pytest.mark.parametrize("metric", NEW)
def test_the_new_readers_find_nothing_in_a_run_that_lacks_the_counters(
        metric):
    reader = manifest.layer_metric(REPO, metric)
    # the parent's ``describe()``: steps and hand-offs, no new counter
    older = {"steps": 10, "egress_inline": 900, "egress_queued": 0}
    assert reader.read(_run(older, {**older, "steps": 90,
                                    "egress_inline": 8900})) is None
    # an untraced run has no ``start`` mark
    assert reader.read(SimpleNamespace(window=SimpleNamespace(
        counters={"end": older}))) is None
    # the keys are there and stand still: nothing went over the unit
    # (0 a step), and no buffer was taken (nothing to read)
    still = {**older, "egress_oversize": 0, "egress_oversize_bytes": 0,
             "egress_pool_takes": 0, "egress_pool_fresh": 0,
             "egress_pool_fresh_bytes": 0}
    read = reader.read(_run(still, {**still, "steps": 90}))
    assert read == (0 if metric == "egress_oversize_per_step" else None)
    # 80 steps, 320 oversize streams, 81 takes of which 3 fresh
    moved = {**still, "steps": 90, "egress_oversize": 320,
             "egress_pool_takes": 81, "egress_pool_fresh": 3}
    assert reader.read(_run(still, moved)) == pytest.approx(
        4.0 if metric == "egress_oversize_per_step" else 3 / 81)
