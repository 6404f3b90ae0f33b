"""scripts/bench_series.py: cross-round merge of BENCH_r*.json into
BENCH_SERIES.md, metric direction inference, and the --gate regression
exit codes (>10% the wrong way vs the previous round fails)."""

import importlib.util
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "bench_series.py")

_spec = importlib.util.spec_from_file_location("bench_series", SCRIPT)
bench_series = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_series)


def _round(path, rnd, section, headline, provenance=None):
    with open(path, "w") as fh:
        json.dump({"round": rnd,
                   section: {"headline": headline, "rows": [],
                             "provenance": provenance or {}}}, fh)


def test_direction_inference():
    assert bench_series.direction("route_cutthrough_msgs_s") == 1
    assert bench_series.direction("churn_forward_ratio") == 1
    assert bench_series.direction("million_users") == 1
    assert bench_series.direction("broadcast_msgs_sec_chip") == 1
    assert bench_series.direction("clean_view_p99_ms") == -1
    assert bench_series.direction("million_rss_mib") == -1
    assert bench_series.direction("million_max_loop_lag_ms") == -1
    assert bench_series.direction("million_storm_catchup_s") == -1
    # counts with no better/worse reading are tracked but never gated
    assert bench_series.direction("chaos_scenarios") == 0


def test_merge_and_markdown(tmp_path):
    _round(tmp_path / "BENCH_r1.json", 1, "route", {"fwd_msgs_s": 100.0})
    _round(tmp_path / "BENCH_r2.json", 2, "route",
           {"fwd_msgs_s": 120.0, "plan_p99_ms": 3.0})
    rounds = bench_series.load_rounds(str(tmp_path))
    assert rounds == {1: {"route": {"fwd_msgs_s": 100.0}},
                      2: {"route": {"fwd_msgs_s": 120.0,
                                    "plan_p99_ms": 3.0}}}
    md = bench_series.render_markdown(rounds)
    assert "## route" in md
    assert "`fwd_msgs_s`" in md and "120" in md
    assert "`plan_p99_ms`" in md


def test_gate_flags_regression_only(tmp_path):
    # throughput -15% and latency +50%: both the wrong way
    _round(tmp_path / "BENCH_r1.json", 1, "route",
           {"fwd_msgs_s": 100.0, "plan_p99_ms": 2.0})
    _round(tmp_path / "BENCH_r2.json", 2, "route",
           {"fwd_msgs_s": 85.0, "plan_p99_ms": 3.0})
    rounds = bench_series.load_rounds(str(tmp_path))
    failed = {(s, m) for s, m, *_ in bench_series.gate(rounds, 0.10)}
    assert failed == {("route", "fwd_msgs_s"), ("route", "plan_p99_ms")}
    # a looser threshold forgives the -15% but not the +50%
    failed = {(s, m) for s, m, *_ in bench_series.gate(rounds, 0.20)}
    assert failed == {("route", "plan_p99_ms")}


def test_gate_improvement_and_new_metrics_pass(tmp_path):
    _round(tmp_path / "BENCH_r1.json", 1, "route", {"fwd_msgs_s": 100.0})
    _round(tmp_path / "BENCH_r2.json", 2, "route",
           {"fwd_msgs_s": 150.0, "brand_new_p99_ms": 9.0})
    rounds = bench_series.load_rounds(str(tmp_path))
    assert bench_series.gate(rounds, 0.10) == []


def test_gate_skips_round_gaps(tmp_path):
    # the metric last appeared two rounds ago: compare against THAT round,
    # not the adjacent one that dropped the section
    _round(tmp_path / "BENCH_r1.json", 1, "route", {"fwd_msgs_s": 100.0})
    _round(tmp_path / "BENCH_r2.json", 2, "other", {"auth_ms": 1.0})
    _round(tmp_path / "BENCH_r3.json", 3, "route", {"fwd_msgs_s": 50.0})
    rounds = bench_series.load_rounds(str(tmp_path))
    fails = bench_series.gate(rounds, 0.10)
    assert [(f[0], f[1], f[2]) for f in fails] == [("route", "fwd_msgs_s", 1)]


def test_gate_waives_cross_host_comparisons(tmp_path):
    """A regression vs a round recorded on a different host (or one that
    predates provenance) is waived — tracked in ``waived``, not a
    failure — while same-fingerprint regressions still gate."""
    host_a = {"platform": "Linux-A", "cpus": 8}
    host_b = {"platform": "Linux-B", "cpus": 1}
    # r1 has no provenance (legacy), r2 on host A, r3 on host B
    _round(tmp_path / "BENCH_r1.json", 1, "other", {"auth_ms": 1.0})
    _round(tmp_path / "BENCH_r2.json", 2, "route",
           {"fwd_msgs_s": 100.0}, provenance=host_a)
    _round(tmp_path / "BENCH_r3.json", 3, "route",
           {"fwd_msgs_s": 50.0}, provenance=host_b)
    rounds = bench_series.load_rounds(str(tmp_path))
    fps = bench_series.load_fingerprints(str(tmp_path))
    waived = []
    assert bench_series.gate(rounds, 0.10, fps, waived) == []
    assert [(w[0], w[1], w[2]) for w in waived] == [("route",
                                                     "fwd_msgs_s", 2)]

    # same host again: the gate re-engages against the host-B baseline
    _round(tmp_path / "BENCH_r4.json", 4, "route",
           {"fwd_msgs_s": 25.0}, provenance=host_b)
    rounds = bench_series.load_rounds(str(tmp_path))
    fps = bench_series.load_fingerprints(str(tmp_path))
    fails = bench_series.gate(rounds, 0.10, fps, [])
    assert [(f[0], f[1], f[2]) for f in fails] == [("route",
                                                    "fwd_msgs_s", 3)]

    # without fingerprints the cross-host pair still gates (legacy call)
    (tmp_path / "BENCH_r4.json").unlink()
    rounds = bench_series.load_rounds(str(tmp_path))
    assert bench_series.gate(rounds, 0.10) != []


def test_cli_gate_exit_codes(tmp_path):
    _round(tmp_path / "BENCH_r1.json", 1, "route", {"fwd_msgs_s": 100.0})
    _round(tmp_path / "BENCH_r2.json", 2, "route", {"fwd_msgs_s": 10.0})
    proc = subprocess.run(
        [sys.executable, SCRIPT, "--root", str(tmp_path), "--gate"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "GATE FAIL" in proc.stdout
    assert (tmp_path / "BENCH_SERIES.md").exists()

    (tmp_path / "BENCH_r2.json").unlink()
    _round(tmp_path / "BENCH_r2.json", 2, "route", {"fwd_msgs_s": 101.0})
    proc = subprocess.run(
        [sys.executable, SCRIPT, "--root", str(tmp_path), "--gate"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "gate OK" in proc.stdout


def test_repo_series_is_current():
    """The committed BENCH_SERIES.md matches what the committed
    BENCH_r*.json files produce — regenerating must be a no-op."""
    rounds = bench_series.load_rounds(REPO)
    assert rounds, "repo has no BENCH_r*.json?"
    committed = open(os.path.join(REPO, "BENCH_SERIES.md")).read()
    assert committed == bench_series.render_markdown(rounds)
