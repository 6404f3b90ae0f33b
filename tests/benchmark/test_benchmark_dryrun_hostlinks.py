"""The cell of four meshed device-plane brokers as a dry run (ISSUE 35):
``hostlinks4-1k.cross-sat`` on an explicit ``JAX_PLATFORMS=cpu`` with the
user count cut to 16 by the harness's test-only argument, untraced and
traced, as ``test_benchmark_dryrun.py`` runs the cells before it; the
launcher driven by hand for what its ``ready`` says and for what it
refuses; and its checks held to topologies made up for them. The
brokers' heartbeat runs live at upstream's 10 s, so a deployment takes
up to that long to mesh: each run has a time limit of its own."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest  # noqa: E402
from benchmark.launchers import control, hostlinks_served  # noqa: E402

RUN = os.path.join(REPO, "benchmark", "run.py")
CELL = "hostlinks4-1k.cross-sat"
NEW = ("links_staged_share", "link_ingress_us_per_frame",
       "link_forward_us_per_send", "mesh_formed_s", "interest_synced_s")


def _env():
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "PYTHONPATH",
                        "JAX_COMPILATION_CACHE_DIR")}
    return {**env, "JAX_PLATFORMS": "cpu"}


def _dry_run(*args):
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seconds", "2",
         "--test-size", "16,2,2", *args],
        capture_output=True, text=True, timeout=150, cwd=REPO, env=_env())
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return proc.stdout


def _final_counters(out):
    said = [ln for ln in out.splitlines()
            if ln.startswith("[bench] counters at the end, every key: ")]
    return json.loads(said[0].split(": ", 1)[1])


def test_the_cell_resolves_to_four_brokers_of_broker1_1k():
    assert manifest.lint(REPO) == []
    cells = manifest.load(REPO)["workloads"]
    assert [w["chips"] for w in cells].count(4) >= 2
    cell = manifest.find_cell(CELL)
    cfg = cell.config
    assert cell.workload["chips"] == cfg["chips"] == cfg["brokers"] == 4
    assert cfg["launcher"] == "hostlinks_served" and cfg["traced_broker"] == 0
    assert cfg["users"] == 1000 and cfg["placement_groups"] == 4
    base = manifest.read_json(REPO, "benchmark/configs/broker1-1k.json")
    for key in ("broker_flags", "marshal_flags", "client_processes",
                "step_modules", "kernels"):
        assert cfg[key] == base[key], key
    assert sorted(cfg["reduced"]) == [
        "brokers", "signature_scheme", "user_transport"]
    assert {"placement", "heartbeat_interval", "sync_interval"} \
        <= set(cfg["assumed"])
    for word in hostlinks_served.chip_env(3).items():
        assert "=".join(word).replace("=3", "=<i>") in cfg["environment"]
    assert any("on any broker" in g for g in cfg["guarantees"])
    # the traffic is mesh4-1k.cross-sat's file, not a copy of it
    assert cell.traffic_file == manifest.find_cell(
        "mesh4-1k.cross-sat").traffic_file
    assert {m["name"] for m in cell.end_to_end} == {
        "delivered_per_s", "broker_cpu_us_per_delivery", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= reported
    assert not reported & {"staged_share", "collective_us_per_tick",
                           "collective_exposed_share"}
    for name in NEW:
        entry = next(m for m in cell.per_layer if m["name"] == name)
        assert entry["layer"] == "broker_links"
        assert entry["workloads"] == [CELL]


def test_untraced_dry_run_reports_the_three_end_to_end_metrics():
    out = _dry_run("--seed", "3500000001", "--trace", "0")
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert all(number == limit for number, limit in line["checks"].values())
    assert line["checks"]["users_connected"] == [16, 16]
    assert line["attempted"] > 100
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4   # one device a broker process
    assert set(line["metrics"]) == {
        "delivered_per_s", "broker_cpu_us_per_delivery", "setup_s"}
    for metric in line["metrics"].values():
        assert metric["value"] > 0 and metric["unit"]
    final = _final_counters(out)
    assert final["users_by_broker"] == [4, 4, 4, 4]
    assert final["users"] == 16 and final["unmirrored"] == 0
    assert final["disabled"] is False and final["device_count"] == 4
    # sums over the four: what crossed a link was staged there, and what
    # was forwarded is what the peers received
    assert 0 < final["link_frames_staged"] < final["frames_staged"]
    assert final["link_frames_forwarded"] >= final["link_frames_staged"]
    assert 0 <= final["mesh_formed_s"] < 30
    assert 0 <= final["interest_synced_s"] < 30


def test_traced_dry_run_reports_the_broker_links_metrics():
    out = _dry_run("--seed", "3500000002", "--trace", "1")
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    for name in NEW:
        assert metrics[name] is not None, (name, metrics)
    # 16 users on 250 topics: few broadcasts have a subscriber elsewhere,
    # so the share is small here and 0.76 only at the cell's own size
    assert 0 < metrics["links_staged_share"] < 1
    assert 0 < metrics["link_ingress_us_per_frame"] < 10_000
    assert 0 < metrics["link_forward_us_per_send"] < 10_000
    assert 0 <= metrics["mesh_formed_s"] < 30
    assert 0 <= metrics["interest_synced_s"] < 30
    for name in ("frames_per_step", "deliveries_per_step",
                 "broker_cpu_cores", "sat_delivery_p99_ms",
                 "sat_step_wall_ms", "ingress_us_per_frame",
                 "egress_us_per_delivery", "pack_cpu_cores_max"):
        assert metrics[name] > 0, (name, metrics)
    assert "staged_share" not in metrics and "delivered_per_s" not in metrics
    spans = [ln for ln in out.splitlines() if ln.startswith("[bench] spans:")]
    said = json.loads(spans[0].split(": ", 1)[1])
    for name in ("links.scan", "links.stage", "links.forward"):
        assert said["spans"][name]["count"] > 0, name
    stats = said["stats"]
    assert stats["links.stage"]["staged"] <= stats["links.scan"]["frames"]
    assert stats["links.forward"]["forwards"] > 0
    assert line["device"]["busy_s"] > 0 and line["device"]["count"] == 4


class Launcher:
    """``hostlinks_served`` started as the harness starts it, spoken to
    by hand."""

    def __init__(self, workdir, config_file=None):
        config_file = config_file or manifest.find_cell(CELL).config_file
        self.proc = subprocess.Popen(
            [sys.executable,
             os.path.join(REPO, manifest.launcher_path("hostlinks_served")),
             "--config", config_file, "--workdir", str(workdir)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True, cwd=REPO, env=_env())

    def event(self):
        while True:
            line = self.proc.stdout.readline()
            assert line, "the launcher's output ended"
            try:
                return json.loads(line)
            except ValueError:
                continue  # a stray print is not protocol

    def ask(self, **cmd):
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()
        return self.event()


def _alive(pid):
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    with open(f"/proc/{pid}/stat") as f:  # a zombie is not alive
        return f.read().rsplit(")", 1)[1].split()[0] != "Z"


def test_the_launcher_says_four_processes_refuses_a_group_that_did_not_land(
        tmp_path):
    launcher = Launcher(tmp_path)
    try:
        ready = launcher.event()
        assert ready["event"] == "ready", ready
        pids = ready["route_pids"]
        assert len(set(pids)) == 4 and launcher.proc.pid == pids[0]
        assert all(_alive(pid) for pid in pids)
        assert ready["device"] == {"platform": "cpu", "kind": "cpu",
                                   "count": 4}
        said = launcher.ask(cmd="counters")
        assert said["event"] == "counters" and said["users"] == 0
        assert said["users_by_broker"] == [0, 0, 0, 0]
        assert 0 <= said["mesh_formed_s"] < 30
        assert launcher.ask(cmd="place", group=0) == {"event": "placed"}
        # nobody connects: group 0 is not behind broker 0, and the
        # launcher says so before it places the next
        refused = launcher.ask(cmd="place", group=1)
        assert refused["event"] == "error", refused
        assert "group 0 did not land on broker 0" in refused["what"]
    finally:
        launcher.proc.send_signal(signal.SIGTERM)
        rc = launcher.proc.wait(timeout=60)
    assert rc == 0
    deadline = time.monotonic() + 10
    while any(_alive(pid) for pid in pids) and time.monotonic() < deadline:
        time.sleep(0.1)
    assert not any(_alive(pid) for pid in pids)


def test_a_marshal_that_will_not_start_is_an_error_that_says_why(tmp_path):
    """Started again twice, then the run fails with what the marshal's
    log ended on: a failed start is never lost with the machine."""
    cfg = dict(manifest.find_cell(CELL).config)
    cfg["marshal_flags"] = [*cfg["marshal_flags"], "--no-such-flag"]
    config_file = tmp_path / "config.json"
    config_file.write_text(json.dumps(cfg))
    (tmp_path / "work").mkdir()
    launcher = Launcher(tmp_path / "work", str(config_file))
    try:
        refused = launcher.event()
        assert refused["event"] == "error", refused
        assert "exited at its start three times" in refused["what"]
        assert "code 2" in refused["what"]
        assert "unrecognized arguments: --no-such-flag" in refused["what"]
    finally:
        assert launcher.proc.wait(timeout=60) == 0  # it stops itself
    with open(tmp_path / "work" / "marshal.log") as log:
        assert log.read().count("unrecognized arguments") == 3


def _topology(ident, users, peers, topics=None, homes=None):
    """A ``/debug/topology`` as far as the launcher reads it: ``peers``
    maps a peer's identity to the topics this broker holds of it."""
    return {"identity": ident, "num_users": users,
            "peers": [{"id": p, "topics": n} for p, n in peers.items()],
            "interest": {"topic_cardinality": {
                str(t): 1 for t in range(users if topics is None else topics)},
                "direct_map_size": homes},
            "device_plane": {"steps": 1, "disabled": False,
                             "unmirrored_users": 0, "warmup_s": 1.0 + users,
                             "device_memory_peak_bytes": 100 * users,
                             "platform": "cpu"}}


def _meshed(users=(4, 4, 4, 4)):
    idents = [f"b{i}" for i in range(len(users))]
    return [_topology(me, mine, {p: users[j] for j, p in enumerate(idents)
                                 if p != me}, homes=sum(users))
            for me, mine in zip(idents, users)]


def test_the_launchers_checks_tell_a_sound_deployment_from_the_others():
    topologies = _meshed()
    assert hostlinks_served.links_missing(topologies) is None
    assert hostlinks_served.interest_missing(topologies) is None
    # a link that has not formed
    cut = _meshed()
    cut[0]["peers"] = cut[0]["peers"][1:]
    cut[1]["peers"] = cut[1]["peers"][:1] + cut[1]["peers"][2:]
    assert "5 of 6 links" in hostlinks_served.links_missing(cut)
    assert "5 of 6 links" in hostlinks_served.interest_missing(cut)
    # a peer's topics, and a user's home, that have not arrived
    stale = _meshed()
    stale[2]["peers"][0]["topics"] = 3
    assert "holds 3 topics of b0, whose users have 4" in \
        hostlinks_served.interest_missing(stale)
    homeless = _meshed()
    homeless[3]["interest"]["direct_map_size"] = 15
    assert "15 of 16 users" in hostlinks_served.interest_missing(homeless)
    # where a group landed
    assert hostlinks_served.misplaced(1, [4, 0, 0, 0], [4, 4, 0, 0]) is None
    for after in ([4, 3, 1, 0], [8, 0, 0, 0], [4, 0, 0, 0]):
        assert "group 1 did not land on broker 1 alone" in \
            hostlinks_served.misplaced(1, [4, 0, 0, 0], after)
    # counts summed, a bool OR-ed, the largest of what a sum would misstate
    planes = [t["device_plane"] for t in _meshed((1, 2, 3, 4))]
    planes[2]["disabled"] = True
    planes[1]["since_pr_99"] = 7  # a key one broker alone says: left out
    assert hostlinks_served.summed(planes) == {
        "steps": 4, "disabled": True, "unmirrored_users": 0,
        "warmup_s": 5.0, "device_memory_peak_bytes": 400}


def test_a_missing_link_is_an_error_event_once_the_limit_is_up(
        tmp_path, monkeypatch):
    monkeypatch.setattr(control, "TOPOLOGY_WAIT_S", 0.3)
    cut = _meshed()
    cut[0]["peers"] = cut[0]["peers"][1:]
    deployment = hostlinks_served.Deployment(
        {"users": 16}, str(tmp_path / "none.sqlite"), [1, 2, 3, 4],
        [t["identity"] for t in cut])
    monkeypatch.setattr(deployment, "topologies", lambda *wait: cut)
    with pytest.raises(hostlinks_served.Unsound, match="links: peers by"):
        deployment.settle(hostlinks_served.links_missing,
                          "the mesh has not formed")
    # the same through the line protocol: interest that has not crossed
    deployment.placed_ns = time.monotonic_ns()
    reply = control.answer({"counters": deployment.counters},
                           json.dumps({"cmd": "counters"}))
    assert reply["event"] == "error"
    assert "interest has not crossed" in reply["what"]


async def test_steering_by_permits_outlives_a_heartbeat(tmp_path):
    """Discovery's load is connections plus outstanding permits, and a
    heartbeat rewrites the first alone: the marshal keeps picking the
    steered broker whatever the others report, and picks by connections
    again once the steering is taken away."""
    from pushcdn_tpu.proto.discovery.base import BrokerIdentifier
    from pushcdn_tpu.proto.discovery.embedded import Embedded
    db = str(tmp_path / "discovery.sqlite")
    idents = [BrokerIdentifier(f"127.0.0.1:{7000 + i}",
                               f"127.0.0.1:{7100 + i}") for i in range(4)]
    handles = [await Embedded.new(db, identity=i) for i in idents]
    marshal = await Embedded.new(db)
    try:
        for handle, users in zip(handles, (0, 250, 250, 250)):
            await handle.perform_heartbeat(users, 60.0)
        assert await marshal.get_with_least_connections() == idents[0]
        hostlinks_served.steer(
            db, [str(i) for k, i in enumerate(idents) if k != 3], 1000)
        assert await marshal.get_with_least_connections() == idents[3]
        for handle in handles[:3]:   # the others' next tick
            await handle.perform_heartbeat(0, 60.0)
        permit = await marshal.issue_permit(idents[3], 30.0, b"a user")
        assert await marshal.get_with_least_connections() == idents[3]
        assert await handles[3].validate_permit(idents[3], permit) \
            == b"a user"
        # no steering permit can be redeemed, at the broker it names
        assert await handles[0].validate_permit(idents[0], -1) is None
        hostlinks_served.steer(db, [], 0)
        assert await marshal.get_with_least_connections() == idents[0]
    finally:
        for handle in (*handles, marshal):
            await handle.close()


def test_a_program_that_does_not_count_its_links_is_refused_at_once(
        tmp_path, monkeypatch, capsys):
    """The parent of ISSUE 35 under this launcher: nothing is started."""
    cell = manifest.find_cell(CELL)
    monkeypatch.setattr(hostlinks_served, "program_counts_its_links",
                        lambda: False)
    monkeypatch.setattr(sys, "argv", [
        "hostlinks_served", "--config", cell.config_file,
        "--workdir", str(tmp_path)])
    t0 = time.monotonic()
    assert hostlinks_served.main() == 2
    assert time.monotonic() - t0 < 2 and os.listdir(tmp_path) == []
    assert "does not count its broker links" in capsys.readouterr().err
