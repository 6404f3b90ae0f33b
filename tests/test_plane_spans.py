"""The profiler spans around the device step and scalar ingress
(ISSUE 24, ``pushcdn_tpu/parallel/spans.py``): a no-op that imports no
JAX until ``runtime.init``; under a profiler session, all eight names,
flat on every thread, joined by ``step``, and conserving the plane's own
counters."""

import asyncio
import glob
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
PLANE = ("plane.take", "plane.h2d", "plane.dispatch", "plane.d2h",
         "plane.encode", "plane.egress")
INGRESS = ("ingress.scan", "ingress.stage")


def test_spans_are_a_noop_that_imports_no_jax_until_runtime_init():
    code = """
import sys
from pushcdn_tpu.parallel import spans
import pushcdn_tpu.broker.tasks.handlers  # the host broker's span user
with spans.span("ingress.stage", frames=3) as sp:
    sp.set_metadata(staged=2)
assert spans.span("a") is spans.span("b") is spans.none("c")
assert "jax" not in sys.modules, "spans imported jax"
from pushcdn_tpu.parallel import runtime
runtime.init("test_plane_spans")
from jax.profiler import TraceAnnotation
assert isinstance(spans.span("plane.h2d", step=1), TraceAnnotation)
assert spans.none("plane.h2d", step=1) is spans._NO_SPAN
print("ok")
"""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0 and proc.stdout.strip() == "ok", \
        proc.stdout[-2000:] + proc.stderr[-2000:]


def _program_spans(trace_dir):
    """``{thread: [(name, start_ns, end_ns, stats), ...]}`` of the
    program's spans, read as the benchmark reads them, and the length in
    ns of the part of the trace that holds them."""
    from benchmark import span_reduce
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = span_reduce.load(path)
    threads = {}
    for s in spans:
        threads.setdefault(s.thread, []).append(
            (s.name, s.start, s.end, s.stats))
    return threads, (max(s.end for s in spans)
                     - min(s.start for s in spans))


async def _burst(client, n, tag):
    """``n`` self-directs written back to back, all read back."""
    await asyncio.gather(*(
        client.send_direct_message(client.public_key, b"%s %d" % (tag, i))
        for i in range(n)))
    got = 0
    async with asyncio.timeout(30):
        while got < n:
            got += len(await client.receive_messages(n - got))


async def _single_plane():
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from pushcdn_tpu.testing import Cluster
    cluster = await Cluster(num_brokers=1, device_plane=DevicePlaneConfig(
        num_user_slots=32, ring_slots=64, frame_bytes=1024,
        batch_window_s=0.002, bypass_max_items=0)).start()
    client = cluster.client(seed=2400, topics=[0])
    await client.ensure_initialized()
    return cluster, client, cluster.brokers[0].device_plane


async def _mesh_group():
    import jax

    from pushcdn_tpu.testing.mesh_cluster import MeshCluster
    cluster = await MeshCluster(num_shards=4,
                                devices=jax.devices()[:4]).start()
    client = await cluster.place_client(seed=2401, shard=0, topics=[0])
    return cluster, client, cluster.group


@pytest.mark.parametrize("deploy", [_single_plane, _mesh_group],
                         ids=["device_plane", "mesh_group"])
async def test_traced_steps_yield_flat_joined_conserving_spans(
        deploy, tmp_path):
    import jax

    from pushcdn_tpu.parallel import spans
    spans.bind()  # what runtime.init does in a device-owning process
    cluster, client, plane = await deploy()
    try:
        # no session: the same code path records nothing (the sums below
        # would be off by this burst if it did)
        await _burst(client, 16, b"untraced")
        staged0, routed0, steps0 = (plane.frames_staged,
                                    plane.messages_routed, plane.steps)
        handoffs0 = (plane.egress_inline, plane.egress_queued,
                     plane.egress_batched)
        drained0 = getattr(plane, "frames_drained", 0)  # no group has it
        described0 = cluster.brokers[0].device_plane.describe()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            for round_ in range(3):
                await _burst(client, 16, b"round%d" % round_)
        finally:
            jax.profiler.stop_trace()
        staged = plane.frames_staged - staged0
        routed = plane.messages_routed - routed0
        steps = plane.steps - steps0
        inline, queued = (plane.egress_inline - handoffs0[0],
                          plane.egress_queued - handoffs0[1])
        batched = plane.egress_batched - handoffs0[2]
        drained = getattr(plane, "frames_drained", 0) - drained0
        described = cluster.brokers[0].device_plane.describe()
    finally:
        client.close()
        await cluster.stop()
    assert staged == routed == 48 and steps >= 3

    threads, trace_ns = _program_spans(str(tmp_path))
    events = [e for evs in threads.values() for e in evs]
    assert {e[0] for e in events} == set(PLANE + INGRESS)

    # flat: on one thread no two of the program's spans overlap
    for evs in threads.values():
        evs.sort(key=lambda e: e[1])
        for (a, _s, a_end, _), (b, b_start, _e, _) in zip(evs, evs[1:]):
            assert a_end <= b_start, (a, b)
    # the two sides of the step run on different threads
    side = {name: {i for i, evs in threads.items()
                   for e in evs if e[0] == name} for name in PLANE + INGRESS}
    loop_thread = side["plane.take"]
    assert len(loop_thread) == 1
    assert side["plane.egress"] == side["ingress.scan"] == \
        side["ingress.stage"] == loop_thread
    for name in ("plane.h2d", "plane.dispatch", "plane.d2h", "plane.encode"):
        assert side[name] and not side[name] & loop_thread, name

    # one step number per step: take, worker phases, egress, in that
    # order, and steps of one plane never overlap
    by_step = {}
    for e in events:
        if e[0] in PLANE:
            by_step.setdefault(e[3]["step"], []).append(e)
    assert sorted(by_step) == list(range(steps0, steps0 + steps))
    last_end = 0.0
    for step in sorted(by_step):
        evs = sorted(by_step[step], key=lambda e: e[1])
        names = [e[0] for e in evs]
        assert names[0] == "plane.take" and names[-1] == "plane.egress"
        assert names.count("plane.take") == names.count("plane.egress") == 1
        assert names.count("plane.dispatch") == 1
        order = [names.index(n) for n in PLANE]
        assert order == sorted(order), names
        assert evs[0][1] >= last_end
        last_end = evs[-1][2]

    # the stats conserve the plane's own counters
    def total(name, stat):
        return sum(e[3][stat] for e in events if e[0] == name)
    assert total("plane.egress", "deliveries") == routed
    # one hand-off per user with deliveries per step, each either written
    # by the pump or queued for the writer (all queued here: the Memory
    # transport's stream has no ``write_nowait``)
    assert (total("plane.egress", "inline"),
            total("plane.egress", "queued")) == (inline, queued)
    assert inline + queued == sum(
        1 for e in events if e[0] == "plane.egress" and e[3]["deliveries"])
    assert total("ingress.stage", "staged") == staged
    assert total("plane.take", "frames") == staged
    assert total("ingress.scan", "frames") >= \
        total("ingress.stage", "frames") >= staged
    waits = [e[3]["ring_wait_us"] for e in events if e[0] == "plane.take"]
    assert all(0 <= w < trace_ns / 1e3 for w in waits), waits
    _drained_conserves(events, drained)
    _batched_conserves(events, batched)
    _uploads_conserve(events, described0, described)


def _uploads_conserve(events, before: dict, after: dict) -> None:
    """The mesh group's ``plane.h2d`` says what it uploaded: ``puts``
    (``device_put`` calls) and ``bytes`` (host bytes handed to them) sum
    to what ``describe()``'s ``h2d_puts`` / ``h2d_bytes`` moved by, one
    put a tick with the membership settled. The single-shard plane's span
    has neither stat, nor its ``describe()`` the keys."""
    h2d = [e[3] for e in events if e[0] == "plane.h2d"]
    if "h2d_puts" not in after:
        assert not any("puts" in st or "bytes" in st for st in h2d)
        return
    assert sum(st["puts"] for st in h2d) == \
        after["h2d_puts"] - before["h2d_puts"]
    assert sum(st["bytes"] for st in h2d) == \
        after["h2d_bytes"] - before["h2d_bytes"]
    assert [st["puts"] for st in h2d] == [1] * len(h2d)
    assert len({st["bytes"] for st in h2d}) <= 2  # full and sliced shapes


def _batched_conserves(events, batched: int) -> None:
    """``plane.egress``'s ``batched`` (hand-offs one native call sent)
    sums to the plane's ``egress_batched`` and is part of ``inline``."""
    egresses = [e[3] for e in events if e[0] == "plane.egress"]
    assert all(0 <= g["batched"] <= g["inline"] for g in egresses), egresses
    assert sum(g["batched"] for g in egresses) == batched


def _drained_conserves(events, drained: int) -> None:
    """``plane.take``'s ``drained`` (frames the pump's drain staged before
    that take) sums to the plane's ``frames_drained`` and never exceeds
    the take's ``frames``."""
    takes = [e[3] for e in events if e[0] == "plane.take"]
    assert all(0 <= t.get("drained", 0) <= t["frames"] for t in takes), takes
    assert sum(t.get("drained", 0) for t in takes) == drained


async def test_traced_takes_report_what_the_drain_staged(
        tmp_path, monkeypatch):
    """Over real TCP links the pump writes the streams itself, so the
    drain engages: frames that reach a socket during ``plane.egress`` are
    the next take's ``drained``, all queued hand-offs leave it 0."""
    import jax

    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from pushcdn_tpu.parallel import spans
    from tests.test_device_plane import (
        _SMALL_PLANE,
        _receive_all,
        _served_over_tcp,
        _socket_of,
        _wire,
        _write_during_egress,
    )
    spans.bind()
    late = [[b"late %d %d" % (r, i) for i in range(r + 1)] for r in range(3)]
    async with _served_over_tcp(
            2830, DevicePlaneConfig(bypass_max_items=0, **_SMALL_PLANE),
            [{0}] * 2) as (broker, clients):
        plane = broker.device_plane
        sock = _socket_of(clients[0])
        armed = []
        _write_during_egress(monkeypatch, sock, armed)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            for r, frames in enumerate(late):
                armed.append(_wire(*frames))
                os.write(sock, _wire(b"round %d" % r))
                got = await _receive_all(clients, 1 + len(frames))
                assert got == [[b"round %d" % r] + frames] * 2
        finally:
            jax.profiler.stop_trace()
        staged, drained, described = (
            plane.frames_staged, plane.frames_drained, plane.describe())
    assert (staged, drained) == (9, 6)
    assert (described["frames_staged"], described["frames_drained"]) == (9, 6)
    threads, _ = _program_spans(str(tmp_path))
    events = [e for evs in threads.values() for e in evs]
    _drained_conserves(events, drained)
    takes = sorted((e for e in events if e[0] == "plane.take"),
                   key=lambda e: e[1])
    assert [(t[3]["frames"], t[3]["drained"]) for t in takes] == [
        (1, 0), (1, 1), (1, 0), (2, 2), (1, 0), (3, 3)]


@pytest.mark.parametrize("deploy", ["device_plane", "mesh_group"])
async def test_traced_egress_reports_what_the_native_batch_sent(
        deploy, tmp_path):
    """Over real TCP links a step whose take found the base lane full
    sends its streams in one native batch (the group's tick: one a
    shard), and ``plane.egress`` says how many: all of that step's
    hand-offs, none of a step with room left."""
    import jax

    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from pushcdn_tpu.parallel import spans
    from tests.test_device_plane import (
        _SMALL_PLANE,
        _receive_all,
        _served_over_tcp,
        _socket_of,
        _wire,
    )
    from tests.test_mesh_group import _RING, _served_group
    spans.bind()
    if deploy == "device_plane":
        lane, users = _SMALL_PLANE["ring_slots"], 2
        served = _served_over_tcp(
            3140, DevicePlaneConfig(bypass_max_items=0, **_SMALL_PLANE),
            [{0}] * users)
    else:
        lane, users = _RING, 4      # one a shard
        served = _served_group(per_shard=1)
    rounds = [[b"round %d %d" % (r, i) for i in range(n)]
              for r, n in enumerate((lane, 3, lane))]
    async with served as (serving, clients):
        if deploy == "device_plane":
            plane = facade = serving.device_plane
        else:
            plane, facade = serving.group, serving.brokers[0].device_plane
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            for frames in rounds:
                os.write(_socket_of(clients[0]), _wire(*frames))
                got = await _receive_all(clients, len(frames))
                assert got == [frames] * users
        finally:
            jax.profiler.stop_trace()
        batched, described = plane.egress_batched, facade.describe()
    assert batched == described["egress_batched"] == 2 * users
    threads, _ = _program_spans(str(tmp_path))
    events = [e for evs in threads.values() for e in evs]
    _batched_conserves(events, batched)
    egresses = sorted((e for e in events if e[0] == "plane.egress"),
                      key=lambda e: e[1])
    assert [(g[3]["inline"], g[3]["queued"], g[3]["batched"])
            for g in egresses] == [
                (users, 0, users), (users, 0, 0), (users, 0, users)]
