"""Upstream's production wiring as a dry run (ISSUE 39):
``prod1-1k.fanout4-sat``, users on TCP+TLS with BLS-BN254 keys, on an
explicit ``JAX_PLATFORMS=cpu`` with the user count cut to 16 by the
harness's test-only argument, untraced and traced, as
``test_benchmark_dryrun_5k.py`` runs the cell before it. The two readers of
the encrypted leg are also held to a run of a commit that lacks the
counters (the parent's case): they find nothing and leave their metric
out."""

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

from benchmark import manifest  # noqa: E402

RUN = os.path.join(REPO, "benchmark", "run.py")
CELL = "prod1-1k.fanout4-sat"
PLAIN = "broker1-1k.fanout4-sat"
NEW = ("tls_write_us_per_handoff", "tls_write_share")
# what ``test_hop_reduce.py`` holds to exactly the three older ``-sat`` cells
PINNED = {"pump_parked_share", "sat_step_hop_ms", "sat_hop_loop_busy_share",
          "ring_full_share"}


def _dry_run(*args):
    from pushcdn_tpu.proto.crypto.signature import BlsBn254Scheme
    if not BlsBn254Scheme.available():
        pytest.skip("the native BLS library does not build here")
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "PYTHONPATH",
                        "JAX_COMPILATION_CACHE_DIR")}
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seconds", "2",
         "--test-size", "16,2,2", *args],
        capture_output=True, text=True, timeout=180, cwd=REPO,
        env={**env, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return proc.stdout


def _final_counters(out):
    said, = [ln for ln in out.splitlines()
             if ln.startswith("[bench] counters at the end, every key: ")]
    return json.loads(said.split(": ", 1)[1])


def test_the_cell_is_the_plain_cell_with_the_users_transport_and_keys():
    cell, plain = manifest.find_cell(CELL), manifest.find_cell(PLAIN)
    assert cell.workload["chips"] == 1 and cell.config["users"] == 1000
    assert cell.traffic_file == plain.traffic_file
    assert manifest.client_settings(cell.config) == {
        "user_transport": "tcp+tls", "signature_scheme": "bls-bn254"}
    # everything but the two keys, their flags and the words about them
    told_apart = {"name", "source", "source_detail", "user_transport",
                  "signature_scheme", "broker_flags", "marshal_flags",
                  "environment", "reduced", "assumed"}
    for key in set(cell.config) | set(plain.config):
        if key not in told_apart:
            assert cell.config[key] == plain.config[key], key
    wiring = ["--user-transport", "tcp+tls", "--scheme", "bls-bn254"]
    assert cell.config["broker_flags"] == ["--device-plane", *wiring]
    assert cell.config["marshal_flags"] == wiring
    assert sorted(cell.config["reduced"]) == ["brokers", "discovery"]
    assert {"users", "client_processes", "certificates",
            "tls_version_and_cipher"} <= set(cell.config["assumed"])
    # it reports what the plain cell reports (but the four whose lists a
    # test of the benchmark's own pins), and the encrypted leg's two
    assert {m["name"] for m in cell.end_to_end} == {
        m["name"] for m in plain.end_to_end} == {
        "delivered_per_s", "broker_cpu_us_per_delivery", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert ({m["name"] for m in plain.per_layer} - PINNED) | set(NEW) \
        <= reported
    assert not any(manifest.applies(m, PLAIN)
                   for m in manifest.load()["per_layer"] if m["name"] in NEW)


def test_untraced_dry_run_reports_the_three_end_to_end_metrics():
    out = _dry_run("--seed", "3900000011", "--trace", "0")
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert line["attempted"] > 100 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {
        "delivered_per_s", "broker_cpu_us_per_delivery", "setup_s"}
    for metric in line["metrics"].values():
        assert metric["value"] > 0 and metric["unit"]
    # 16 users connected to a broker that listens for TLS alone, every
    # hand-off over an encrypting stream and none in the native batch
    final = _final_counters(out)
    assert final["users"] == 16 and final["unmirrored"] == 0
    assert final["egress_tls"] == \
        final["egress_inline"] + final["egress_queued"] > 100
    assert final["egress_tls_inline"] == final["egress_inline"]
    assert final["egress_batched"] == 0 and final["egress_tls_write_us"] > 0


def test_traced_dry_run_reports_the_encrypted_legs_two_metrics():
    out = _dry_run("--seed", "3900000012", "--trace", "1")
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["tls_write_us_per_handoff"] > 0
    assert 0 < metrics["tls_write_share"] <= 1
    assert metrics["egress_batched_share"] == 0
    assert metrics["egress_inline_share"] > 0.9
    for name in ("staged_share", "frames_per_step", "deliveries_per_step",
                 "sat_step_wall_ms", "sat_delivery_p99_ms", "connect_s",
                 "egress_us_per_delivery", "broker_cpu_cores"):
        assert metrics[name] > 0, (name, metrics)
    assert "delivered_per_s" not in metrics and "setup_s" not in metrics
    assert _final_counters(out)["egress_batched"] == 0
    assert line["device"]["busy_s"] > 0


@pytest.mark.parametrize("metric", NEW)
def test_the_new_readers_find_nothing_in_a_run_that_lacks_the_counters(
        metric):
    reader = manifest.layer_metric(REPO, metric)

    def run(start, end):
        return SimpleNamespace(window=SimpleNamespace(
            counters={"start": start, "end": end}))
    # the parent's ``describe()``: the pump's account, no ``egress_tls_*``
    older = {"egress_inline": 10, "egress_queued": 0, "pump_egress_us": 500}
    assert reader.read(run(older, {**older, "egress_inline": 90,
                                   "pump_egress_us": 9500})) is None
    # an untraced run has no ``start`` mark
    assert reader.read(SimpleNamespace(window=SimpleNamespace(
        counters={"end": older}))) is None
    # plain users: the keys are there and stand still
    plain = {**older, "egress_tls": 0, "egress_tls_inline": 0,
             "egress_tls_write_us": 0}
    assert reader.read(run(plain, {**plain, "pump_egress_us": 9500})) is None
    # TLS users: 80 inline writes of 60 us in 9 ms of the egress state
    moved = {**plain, "egress_tls": 90, "egress_tls_inline": 90,
             "egress_tls_write_us": 4800, "pump_egress_us": 9500}
    tls = {**plain, "egress_tls": 10, "egress_tls_inline": 10}
    assert reader.read(run(tls, moved)) == pytest.approx(
        60.0 if metric == "tls_write_us_per_handoff" else 4800 / 9000)
