"""The committee-size cell as a dry run (ISSUE 27):
``broker1-5k.global5k-steady`` on an explicit ``JAX_PLATFORMS=cpu`` with
the user count cut to 16 by the harness's test-only argument, untraced
and traced, as ``test_benchmark_dryrun.py`` runs the cells before it.

Two things a 16-user CPU run cannot show at the cell's own rate are held
another way. At its 30 frames/s every frame meets an idle plane and the
program's idle bypass host-routes it, so the device path of the cell's
mix is driven by the harness's own ``--sweep`` at a rate that keeps a
step in flight. And the CPU backend runs the XLA twin, never the Mosaic
kernel whose calls ``delivery_table_rows`` reads, so the reader is held
to the recorded v5e trace and to a call at the cell's 8,192 rows."""

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

from benchmark import manifest, trace_reduce  # noqa: E402

RUN = os.path.join(REPO, "benchmark", "run.py")
CELL = "broker1-5k.global5k-steady"
FIXTURE = os.path.join(REPO, "benchmark", "fixtures",
                       "global_steady_3s.xplane.pb")


def _dry_run(*args):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "PYTHONPATH",
                        "JAX_COMPILATION_CACHE_DIR")}
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seconds", "2",
         "--test-size", "16,2,2", *args],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**env, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return proc.stdout


def test_the_cell_resolves_to_the_committee_size():
    cell = manifest.find_cell(CELL)
    assert cell.workload["chips"] == 1 and cell.config["users"] == 5000
    assert cell.config["launcher"] == "broker_served"
    base = manifest.read_json(REPO, "benchmark/configs/broker1-1k.json")
    for key in ("broker_flags", "marshal_flags", "client_processes",
                "placement_groups", "step_modules", "kernels", "guarantees"):
        assert cell.config[key] == base[key], key
    assert {m["name"] for m in cell.end_to_end} == {
        "delivery_p50_ms", "broker_cpu_us_per_delivery", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert {"delivery_table_rows", "staged_share", "gen_late_p99_ms",
            "delivery_kernel_roofline", "step_device_us", "step_d2h_ms",
            "steady_delivery_p99_ms", "egress_us_per_delivery"} <= reported
    # global-steady's shape at this committee: all on Global, a tenth on DA
    subs = cell.traffic["subscriptions"]
    assert subs[0] == {"users": "all", "topic": {"fixed": 0}}
    assert subs[1] == {"users": [0, 500], "topic": {"fixed": 1}}
    steady = manifest.read_json(REPO, manifest.traffic_path("global-steady"))
    (flow,), (theirs,) = cell.traffic["flows"], steady["flows"]
    assert flow["mix"] == theirs["mix"]
    assert flow["publishers"] == theirs["publishers"] == 8
    assert flow["loop"]["kind"] == "open" and flow["loop"]["rate_per_s"] > 0


def test_untraced_dry_run_reports_the_three_end_to_end_metrics():
    out = _dry_run("--seed", "2700000001", "--trace", "0")
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert line["attempted"] > 500 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {
        "delivery_p50_ms", "broker_cpu_us_per_delivery", "setup_s"}
    for metric in line["metrics"].values():
        assert metric["value"] > 0 and metric["unit"]


def test_traced_dry_run_reports_the_cells_per_layer_metrics():
    out = _dry_run("--seed", "2700000002", "--trace", "1")
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    for name in ("gen_late_p99_ms", "steady_delivery_p99_ms", "connect_s",
                 "step_wall_ms", "step_handoff_ms", "step_h2d_ms",
                 "step_dispatch_ms", "step_d2h_ms", "ring_wait_ms",
                 "step_device_us", "egress_us_per_delivery"):
        assert metrics[name] > 0, (name, metrics)
    assert "staged_share" in metrics and "steps_per_s" in metrics
    assert "delivery_p50_ms" not in metrics and \
        "delivery_p99_ms" not in metrics and "sat_step_wall_ms" not in metrics
    # no Mosaic kernel on this backend: both readers of its calls find
    # nothing and leave their metric out, neither raises
    assert "delivery_table_rows" not in metrics
    assert "delivery_kernel_roofline" not in metrics
    assert line["device"]["busy_s"] > 0


def test_the_cells_mix_rides_the_device_path_when_a_step_stays_in_flight():
    out = _dry_run("--seed", "2700000003", "--sweep", "4000")
    rows = [json.loads(ln) for ln in out.splitlines()
            if ln.startswith('{"sweep_rate_per_s"')]
    assert len(rows) == 1, out[-3000:]
    row = rows[0]
    assert row["failed"] == 0 and row["problems"] == 0
    assert row["staged_share"] > 0.9 and row["steps"] > 10
    assert row["attempted"] == 16 * row["frames"]  # both topics reach all 16


def test_delivery_table_rows_reads_the_largest_table_in_the_trace():
    reader = manifest.layer_metric(REPO, "delivery_table_rows")
    reduced = trace_reduce.reduce(trace_reduce.load(FIXTURE),
                                  kernels=["delivery_matrix_pallas"])
    config = {"kernels": {"delivery": "delivery_matrix_pallas"}}

    def read(trace, cfg=config):
        return reader.read(SimpleNamespace(
            window=SimpleNamespace(trace=trace), config=cfg))

    assert read(reduced) == 1024          # the 1,000-user broker's step
    row = reduced["kernels"]["delivery_matrix_pallas"]
    hlo = next(iter(row["calls"]))
    grown = hlo.replace("s32[1024,1024]", "s32[8192,1024]").replace(
        "u32[1024,8]", "u32[8192,8]")
    assert grown != hlo
    both = {"kernels": {"delivery_matrix_pallas": {
        **row, "calls": {hlo: [3, 1e-3], grown: [2, 2e-3]}}}}
    assert read(both) == 8192             # the largest, not the commonest
    # the same calls price the roofline at the rows they ran at
    from benchmark import peaks
    roofline = manifest.layer_metric(REPO, "delivery_kernel_roofline")
    share = roofline.read(SimpleNamespace(
        window=SimpleNamespace(trace={"kernels": {"delivery_matrix_pallas": {
            "count": 5, "seconds": 7e-3, "calls": both["kernels"][
                "delivery_matrix_pallas"]["calls"]}}}),
        config=config, device={"kind": "TPU v5 lite"}))
    assert share == pytest.approx(100 * (
        3 * peaks.delivery_min_bytes(1024, 1024, 8)
        + 2 * peaks.delivery_min_bytes(8192, 1024, 8)) / 819e9 / 7e-3)
    # nothing to read is nothing, never an error: an older program, a
    # CPU run, a run without a trace, a call whose text has no shapes
    assert read(None) is None
    assert read({"kernels": {}}) is None
    assert read({"kernels": {"delivery_matrix_pallas": {
        "count": 0, "seconds": 0.0, "calls": {}}}}) is None
    assert read(reduced, cfg={}) is None
    assert read({"kernels": {"delivery_matrix_pallas": {
        "count": 1, "seconds": 1e-4,
        "calls": {"%k = s32[4] custom-call()": [1, 1e-4]}}}}) is None
