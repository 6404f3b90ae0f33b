"""What the launchers and the client processes take from a configuration
(ISSUE 32): a deployment whose users come over TCP+TLS, with Ed25519 or
BLS-BN254 keys, added to a scratch copy by files alone and driven as a dry
run on an explicit ``JAX_PLATFORMS=cpu`` at 16 users; the same run with the
timed path broken underneath, which has to come out as not correct; and a
launcher that keeps asking a busy broker for its counters, and says so when
it gives up."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest  # noqa: E402
from benchmark.launchers import broker_served, control  # noqa: E402

CELL = "broker1-1k.fanout4-sat"


def scratch_copy(root):
    """The benchmark's own files as a copy under ``root``, the program
    beside them as it is (its sources and what it has built are linked,
    not copied)."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    os.makedirs(os.path.join(REPO, ".build"), exist_ok=True)
    for name in ("pushcdn_tpu", "native", ".build"):
        os.symlink(os.path.join(REPO, name), os.path.join(root, name))
    return str(root)


@pytest.fixture
def scratch(tmp_path):
    return scratch_copy(tmp_path / "copy")


def add_deployment(root, **changed):
    """``broker1-1k``'s deployment with some keys changed, under
    ``fanout4-sat``: a new configuration file, a new cell."""
    cfg = manifest.read_json(root, "benchmark/configs/broker1-1k.json")
    cfg.update(name="wired", source="https://example.org/wired", **changed)
    with open(os.path.join(root, "benchmark/configs/wired.json"), "w") as f:
        json.dump(cfg, f)
    m = manifest.load(root)
    m["configs"].append({
        "name": "wired", "source": cfg["source"],
        "file": "benchmark/configs/wired.json",
        "reduced": sorted(cfg["reduced"]), "why": "a scratch deployment"})
    m["workloads"].append({
        "name": "wired.fanout4-sat", "config": "wired",
        "traffic": "fanout4-sat", "chips": 1, "why": "a scratch cell"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("wired.fanout4-sat")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)


def dry_run(root, seed, *size):
    """The scratch cell as the driver would run it, on an explicit CPU at
    16 users unless ``size`` says otherwise."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "PYTHONPATH",
                        "JAX_COMPILATION_CACHE_DIR")}
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"),
         "--workload", "wired.fanout4-sat", "--seed", seed, "--trace", "0",
         *(size or ("--seconds", "2", "--test-size", "16,2,2"))],
        capture_output=True, text=True, timeout=120, cwd=root,
        env={**env, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return proc


@pytest.mark.parametrize("transport,scheme", [
    ("tcp+tls", "ed25519"), ("tcp+tls", "bls-bn254"), ("tcp", "ed25519")])
def test_dry_run_with_the_users_transport_and_scheme_from_the_configuration(
        scratch, transport, scheme):
    if scheme == "bls-bn254":
        from pushcdn_tpu.proto.crypto.signature import BlsBn254Scheme
        if not BlsBn254Scheme.available():
            pytest.skip("the native BLS library does not build here")
    flags = ["--user-transport", transport, "--scheme", scheme]
    base = manifest.read_json(REPO, "benchmark/configs/broker1-1k.json")
    add_deployment(
        scratch, user_transport=transport, signature_scheme=scheme,
        broker_flags=["--device-plane", *flags], marshal_flags=flags,
        reduced={k: v for k, v in base["reduced"].items() if not (
            (k, transport) == ("user_transport", "tcp+tls")
            or (k, scheme) == ("signature_scheme", "bls-bn254"))})
    assert manifest.lint(scratch) == []
    proc = dry_run(scratch, "3200000011")
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, proc.stdout[-3000:]
    assert line["attempted"] > 100 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {
        "delivered_per_s", "broker_cpu_us_per_delivery", "setup_s"}
    # what the program says of its own links, through the launcher's
    # pass-through: the step's streams all left by the pump's own hand,
    # and in one native batch only where a link is a plain TCP socket. A
    # broker that listens for TLS takes no plain client and a plain
    # broker no TLS client, so 16 users connected and ``egress_batched``
    # at 0 are users on TLS; the plain case is the control of the two.
    said = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("[bench] counters at the end, every key: ")]
    final = json.loads(said[0].split(": ", 1)[1])
    assert final["users"] == 16 and final["unmirrored"] == 0
    assert final["egress_inline"] + final["egress_queued"] > 100
    assert (final["egress_batched"] > 100) == (transport == "tcp"), final
    if transport != "tcp":
        assert final["egress_batched"] == 0


# A launcher of the test's own: ``broker_served`` with the timed path
# broken underneath. Every seventh egress of the device plane has one
# cell of its delivery matrix altered where it is produced, between the
# step's decision and the encoder: a delivery dropped (the guarantee "to
# every user subscribed at publish time"), or one made to a user who is
# not owed it (the guarantee "and to no other").
FAULTY_LAUNCHER = '''
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark.launchers import broker_served
from pushcdn_tpu import native

with open(sys.argv[sys.argv.index("--config") + 1]) as f:
    FAULT = json.load(f)["fault"]
real, calls = native.egress_encode, [0]


def faulty(deliver, lengths, blocks):
    calls[0] += 1
    users, frames = np.nonzero(deliver)
    if calls[0] % 7 == 0 and len(users):
        deliver = deliver.copy()
        if FAULT == "drop":
            deliver[users[0], frames[0]] = False
        else:
            live = users.max() + 1  # rows in use: the connected users
            other = next(u for u in range(live)
                         if not deliver[u, frames[0]])
            deliver[other, frames[0]] = True
    return real(deliver, lengths, blocks)


native.egress_encode = faulty
sys.exit(broker_served.main())
'''


def add_faulty_deployment(root, fault):
    with open(os.path.join(root, "benchmark/launchers/broker_faulty.py"),
              "w") as f:
        f.write(FAULTY_LAUNCHER)
    add_deployment(root, launcher="broker_faulty", fault=fault)


@pytest.mark.parametrize("fault,fails,may_fail", [
    ("drop", {"streams_differing", "deliveries_missing"}, set()),
    # where the frame was a direct or a probe, the client that got it
    # says so itself as well
    ("misdeliver", {"streams_differing"}, {"misdirected"})])
def test_a_run_with_the_timed_path_broken_underneath_is_not_correct(
        scratch, fault, fails, may_fail):
    add_faulty_deployment(scratch, fault)
    assert manifest.lint(scratch) == []
    proc = dry_run(scratch, "3200000012")
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False, proc.stdout[-3000:]
    assert list(line)[-1] == "checks"
    failing = {name for name, (number, limit) in line["checks"].items()
               if number != limit}
    assert fails <= failing <= fails | may_fail, line["checks"]
    assert line["checks"]["streams_differing"][0] >= 3
    # each number compared beside its limit, the last lines on standard error
    last = proc.stderr.strip().splitlines()[-len(line["checks"]):]
    assert last == [f"check {name}: {number} (limit {limit})"
                    for name, (number, limit) in line["checks"].items()]
    assert "[bench] differs from the reference: user " in proc.stdout


def test_a_launcher_waits_for_a_busy_broker_and_says_when_it_gives_up(
        monkeypatch):
    plane = {"steps": 3, "frames_staged": 20, "messages_routed": 20,
             "disabled": False, "programs": 6, "cache_hits": 6,
             "cache_misses": 0, "compile_s": 0.1, "warmup_s": 0.5,
             "unmirrored_users": 0, "egress_inline": 2, "user_slots": 1024,
             "platform": "cpu", "kernels": {"latency[8]": "xla"},
             "compile_cache": None}
    asked = []

    def third_time(_port, timeout):
        asked.append(timeout)
        return {"num_users": 16, "device_plane": plane} \
            if len(asked) == 3 else None

    monkeypatch.setattr(broker_served, "_topology", third_time)
    monkeypatch.setattr(control, "memory_peak_bytes", lambda: 0)
    handlers = {"counters": lambda _cmd: broker_served.counters(0, 2.0)}
    reply = control.answer(handlers, '{"cmd": "counters"}')
    assert len(asked) == 3 and all(0 < t <= 2.0 for t in asked)
    assert reply["event"] == "counters" and reply["users"] == 16
    # the nine keys the harness reads, by the program's names and values,
    # and every other number the program says beside them; no text, no
    # nested object
    for key in ("steps", "frames_staged", "messages_routed", "disabled",
                "programs", "cache_hits", "cache_misses", "compile_s",
                "warmup_s", "egress_inline", "user_slots", "compile_cache"):
        assert reply[key] == plane[key], key
    assert "platform" not in reply and "kernels" not in reply
    assert reply["unmirrored"] == 0 and reply["memory_peak_bytes"] == 0

    # a broker that never answers: an ``error`` event that says so, within
    # the limit (and not a TypeError from reading None)
    monkeypatch.setattr(broker_served, "_topology",
                        lambda _port, timeout: time.sleep(0.05))
    t0 = time.monotonic()
    reply = control.answer(handlers, '{"cmd": "counters"}')
    assert 1.5 < time.monotonic() - t0 < 3.0
    assert reply["event"] == "error"
    assert "NoAnswer" in reply["what"] and "/debug/topology" in reply["what"]
    assert "no answer within 2 s" in reply["what"]
    # both launchers share the one figure, and the parent waits beyond it
    assert control.TOPOLOGY_WAIT_S == 60.0
