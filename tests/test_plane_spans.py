"""The profiler spans around the device step and scalar ingress
(ISSUE 24, ``pushcdn_tpu/parallel/spans.py``): a no-op that imports no
JAX until ``runtime.init``; under a profiler session, all eight names,
flat on every thread, joined by ``step``, and conserving the plane's own
counters. And the loop's side of the window (ISSUE 37), as cumulative
counters in ``describe()``: the pump's state account, the full ring, the
writers' wait and writes, the loop's lag and the profiler's tick."""

import asyncio
import glob
import os
import subprocess
import sys
import time

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
                     plane.egress_batched, plane.egress_batched_short)
        drained0 = getattr(plane, "frames_drained", 0)  # no group has it
        described0 = cluster.brokers[0].device_plane.describe()
        t0_ns = time.monotonic_ns()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            # a parked quarter second, so that the interval is long
            # beside the state that stands open at either end
            await asyncio.sleep(0.25)
            for round_ in range(3):
                await _burst(client, 16, b"round%d" % round_)
            # while the pump has been parked for microseconds, as it had
            # at ``described0``: the open state is not yet credited
            described = cluster.brokers[0].device_plane.describe()
            elapsed_us = (time.monotonic_ns() - t0_ns) / 1e3
        finally:
            jax.profiler.stop_trace()
        staged = plane.frames_staged - staged0
        routed = plane.messages_routed - routed0
        steps = plane.steps - steps0
        inline, queued = (plane.egress_inline - handoffs0[0],
                          plane.egress_queued - handoffs0[1])
        batched = plane.egress_batched - handoffs0[2]
        short = plane.egress_batched_short - handoffs0[3]
        drained = getattr(plane, "frames_drained", 0) - drained0
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
    _batched_conserves(events, batched, short)
    _uploads_conserve(events, described0, described)
    _account_conserves(events, described0, described, elapsed_us)


PUMP_STATES = ("parked", "gate", "drain", "take", "worker", "egress")


def _pump_us(described: dict) -> dict:
    return {state: described[f"pump_{state}_us"] for state in PUMP_STATES}


def _account_conserves(events, before: dict, after: dict,
                       elapsed_us: float) -> None:
    """The pump's states partition its wall time: the six counters moved
    by what the clock moved by between the two ``describe()`` calls (the
    pump stood parked at both, and the open state is not yet credited);
    a traced take says what lay between it and the egress before it, so
    the takes' stats sum to what the three counters moved by (but for the
    round after the last take, which found nothing staged), and a traced
    period closes: take to take is take + worker + egress + those
    three."""
    moved = {state: us - _pump_us(before)[state]
             for state, us in _pump_us(after).items()}
    assert all(us >= 0 for us in moved.values()), moved
    assert sum(moved.values()) == pytest.approx(elapsed_us, rel=0.02)
    busy = after["worker_busy_us"] - before["worker_busy_us"]
    assert 0 < busy <= moved["worker"]
    assert after["worker_busy_us"] <= after["pump_worker_us"]
    takes = sorted((e for e in events if e[0] == "plane.take"),
                   key=lambda e: e[1])
    egress = {e[3]["step"]: e for e in events if e[0] == "plane.egress"}
    for state in ("parked", "gate", "drain"):
        said = sum(t[3][f"{state}_us"] for t in takes)
        assert said == pytest.approx(moved[state], abs=0.02 * elapsed_us), \
            state
    if "frames_drained" not in after:
        assert moved["drain"] == 0  # no group has a drain
    periods = parts = 0.0
    for take, nxt in zip(takes, takes[1:]):
        done = egress[take[3]["step"]]
        periods += nxt[1] - take[1]
        parts += (done[2] - take[1]) + 1e3 * (
            nxt[3]["parked_us"] + nxt[3]["gate_us"] + nxt[3]["drain_us"])
    assert parts == pytest.approx(periods, rel=0.02)


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


def _batched_conserves(events, batched: int, short: int) -> None:
    """``plane.egress``'s ``batched`` (hand-offs one native call sent)
    sums to the plane's ``egress_batched`` and is part of ``inline``; its
    ``short`` (of those, the ones settled one by one) sums to
    ``egress_batched_short`` and is part of ``batched``."""
    egresses = [e[3] for e in events if e[0] == "plane.egress"]
    assert all(0 <= g["short"] <= g["batched"] <= g["inline"]
               for g in egresses), egresses
    assert sum(g["batched"] for g in egresses) == batched
    assert sum(g["short"] for g in egresses) == short


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


@pytest.mark.parametrize("settled", ["together", "each"])
@pytest.mark.parametrize("deploy", ["device_plane", "mesh_group"])
async def test_traced_egress_reports_what_the_native_batch_sent(
        deploy, settled, tmp_path, monkeypatch):
    """Over real TCP links a step whose take found the base lane full
    sends its streams in one native batch (the group's tick: one a
    shard), and ``plane.egress`` says how many: all of that step's
    hand-offs, none of a step with room left; and how many of them were
    ``short``, settled one by one: none where every send took its whole
    stream, all of them where the comparison that says so finds none."""
    import jax

    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from pushcdn_tpu.parallel import spans
    from tests.test_device_plane import (
        _SMALL_PLANE,
        _never_whole,
        _receive_all,
        _record_batches,
        _served_over_tcp,
        _socket_of,
        _wire,
    )
    from tests.test_mesh_group import _RING, _served_group
    spans.bind()
    if settled == "each":
        _record_batches(monkeypatch, after=_never_whole)
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
        batched, short = plane.egress_batched, plane.egress_batched_short
        described = facade.describe()
    assert batched == described["egress_batched"] == 2 * users
    assert short == described["egress_batched_short"] == \
        (batched if settled == "each" else 0)
    threads, _ = _program_spans(str(tmp_path))
    events = [e for evs in threads.values() for e in evs]
    _batched_conserves(events, batched, short)
    egresses = sorted((e for e in events if e[0] == "plane.egress"),
                      key=lambda e: e[1])
    assert [(g[3]["inline"], g[3]["queued"], g[3]["batched"])
            for g in egresses] == [
                (users, 0, users), (users, 0, 0), (users, 0, users)]
    assert [g[3]["short"] for g in egresses] == [
        g[3]["batched"] if settled == "each" else 0 for g in egresses]


# ---- the loop's side of the window (ISSUE 37) ------------------------------

@pytest.mark.parametrize("deploy", [_single_plane, _mesh_group],
                         ids=["device_plane", "mesh_group"])
async def test_pump_account_partitions_the_pumps_wall_time(deploy):
    """Untraced, as a window's two marks read it: between two
    ``describe()`` calls the six state counters move by what
    ``monotonic_ns`` moved by, an idle stretch is ``parked``, and the
    step's wall on the worker thread lies inside the pump's wait for
    it."""
    cluster, client, _plane = await deploy()
    facade = cluster.brokers[0].device_plane
    try:
        await _burst(client, 16, b"first")
        before, t0_ns = facade.describe(), time.monotonic_ns()
        await asyncio.sleep(0.3)
        for round_ in range(4):
            await _burst(client, 16, b"round%d" % round_)
        after = facade.describe()
        elapsed_us = (time.monotonic_ns() - t0_ns) / 1e3
    finally:
        client.close()
        await cluster.stop()
    moved = {state: us - _pump_us(before)[state]
             for state, us in _pump_us(after).items()}
    assert all(isinstance(us, int) and us >= 0 for us in moved.values())
    assert sum(moved.values()) == pytest.approx(elapsed_us, rel=0.02)
    assert moved["parked"] >= 0.3e6 * 0.98
    assert min(moved["take"], moved["worker"], moved["egress"]) > 0
    busy = after["worker_busy_us"] - before["worker_busy_us"]
    assert 0 < busy <= moved["worker"]
    assert after["steps"] - before["steps"] >= 4
    # both planes say how often a step was batched off saturation and a
    # take paced; never here (no step batched: the Memory transport
    # queues every stream, and the group paces none)
    assert after["egress_offsat_batched"] == after["pump_paced_steps"] \
        == after["pump_paced_us"] == 0


@pytest.mark.parametrize("deploy", [_single_plane, _mesh_group],
                         ids=["device_plane", "mesh_group"])
async def test_a_full_ring_is_counted_a_frame_once_and_every_time_it_says_so(
        deploy):
    """A receive batch longer than the lanes hold, staged in one call:
    the frames held back are ``stage_full_frames``, each ``FULL`` handed
    back (the batch's and every retry's) is ``stage_full_results``, and
    the retry that stages them later moves neither."""
    from pushcdn_tpu.broker.staging import StageResult
    from pushcdn_tpu.broker.tasks.handlers import _stage_with_backpressure
    from pushcdn_tpu.proto.limiter import Bytes
    from pushcdn_tpu.proto.message import Broadcast, deserialize, serialize
    cluster, client, _plane = await deploy()
    facade = cluster.brokers[0].device_plane
    try:
        before = facade.describe()
        items = []
        for i in range(150):  # the lanes hold 128 (32 a group's shard)
            raw = Bytes(serialize(Broadcast(topics=[0],
                                            message=b"full %d" % i)))
            items.append((deserialize(raw.data), raw))
        results = facade.stage_batch(items)
        held = [item for item, res in zip(items, results)
                if res == StageResult.FULL]
        assert held and results.count(StageResult.STAGED) == \
            len(items) - len(held)
        said = facade.describe()
        assert said["stage_full_frames"] - before["stage_full_frames"] == \
            said["stage_full_results"] - before["stage_full_results"] == \
            len(held)
        # the pump has not run: the ring is as full as it was
        assert facade.try_stage(*held[0]) == StageResult.FULL
        for message, raw in held:
            assert await _stage_with_backpressure(facade, message, raw) \
                == StageResult.STAGED
        got = 0
        async with asyncio.timeout(30):
            while got < len(items):
                got += len(await client.receive_messages(len(items) - got))
        after = facade.describe()
    finally:
        client.close()
        await cluster.stop()
    assert after["stage_full_frames"] - before["stage_full_frames"] == \
        len(held)
    assert after["stage_full_results"] - before["stage_full_results"] > \
        len(held)
    assert after["frames_staged"] - before["frames_staged"] == len(items)


class _CountingWriter:
    """A ``StreamWriter`` that counts the bytes handed to ``write`` and
    ``writelines`` and is the real one in everything else."""

    def __init__(self, writer):
        self._writer = writer
        self.handed = 0
        self.calls = 0

    def write(self, data):
        self.handed += len(data)
        self.calls += 1
        self._writer.write(data)

    def writelines(self, bufs):
        bufs = list(bufs)
        self.handed += sum(len(b) for b in bufs)
        self.calls += 1
        self._writer.writelines(bufs)

    def __getattr__(self, name):
        return getattr(self._writer, name)


async def test_writer_writes_are_counted_with_the_bytes_they_handed_over():
    """Over a real TCP link, every ``write`` / ``writev`` of the writer
    task is one of ``writer_writes``, ``writer_write_bytes`` is what the
    transport was handed meanwhile and ``writer_write_us`` the time
    inside those calls; the inline path (``write_nowait``) counts under
    none of them."""
    from pushcdn_tpu.proto import metrics
    from pushcdn_tpu.proto.message import Direct
    from pushcdn_tpu.proto.transport import Tcp
    listener = await Tcp.bind("127.0.0.1:0")
    connecting = asyncio.create_task(
        Tcp.connect(f"127.0.0.1:{listener.bound_port}"))
    server = await (await asyncio.wait_for(listener.accept(), 10)).finalize()
    client = await connecting
    counting = server._stream.writer = _CountingWriter(server._stream.writer)
    sizes = [10, 1000, 70_000] + [300] * 40  # one, one, unbatched, a batch
    try:
        before = metrics.loop_account()
        t0_ns = time.monotonic_ns()
        await server.send_message(Direct(recipient=b"r",
                                         message=b"x" * sizes[0]))
        await server.send_message(Direct(recipient=b"r",
                                         message=b"x" * sizes[1]))
        await asyncio.gather(*(
            server.send_message(Direct(recipient=b"r", message=b"x" * n))
            for n in sizes[2:]))
        got = []
        async with asyncio.timeout(30):
            while len(got) < len(sizes):
                got.append(len((await client.recv_message()).message))
        assert sorted(got) == sorted(sizes)
        after = metrics.loop_account()
        elapsed_us = (time.monotonic_ns() - t0_ns) / 1e3
        calls, handed = counting.calls, counting.handed
        # the non-awaiting path hands bytes over uncounted
        assert server._stream.write_nowait(b"inline")
        assert metrics.loop_account()["writer_writes"] == \
            after["writer_writes"]
    finally:
        client.close()
        server.close()
        await listener.close()
    moved = {key: after[key] - before[key] for key in (
        "writer_writes", "writer_write_bytes", "writer_write_us")}
    # the client's own writer shares the process: it wrote nothing here
    assert moved["writer_writes"] == calls >= 3
    assert moved["writer_write_bytes"] == handed > sum(sizes)
    assert 0 < moved["writer_write_us"] < elapsed_us


async def test_writer_counters_are_the_delay_histograms_own_totals():
    """``describe()`` reads the writers' wait off the family the program
    already keeps: every class's count and sum, and the observations
    above its 0.5 s bucket."""
    from pushcdn_tpu.proto import metrics
    cluster, client, _plane = await _single_plane()
    facade = cluster.brokers[0].device_plane
    try:
        before = facade.describe()
        await _burst(client, 16, b"queued")  # Memory links: all queued
        classes = metrics.WRITER_QUEUE_DELAY_CLS
        classes[1].observe(0.5)    # the bucket's own edge: not over it
        classes[2].observe(0.7)
        classes[0].observe(6.0)    # beyond the last bucket
        after = facade.describe()
    finally:
        client.close()
        await cluster.stop()
    assert after["writer_dequeues"] == sum(h.total for h in classes)
    assert after["writer_wait_us"] == int(
        sum(h.sum for h in classes) * 1e6)
    assert after["writer_dequeues"] - before["writer_dequeues"] >= 16 + 3
    assert after["writer_wait_us"] - before["writer_wait_us"] >= 7.2e6
    assert after["writer_wait_over_500ms"] \
        - before["writer_wait_over_500ms"] == 2


async def test_loop_lag_account_grows_by_a_deliberate_block():
    """The lag sampler's two sums: absent until a sampler runs, then a
    0.3 s synchronous block of the loop shows as at least 0.2 s more lag
    over the samples taken meanwhile."""
    from pushcdn_tpu.proto import metrics
    sampler = asyncio.create_task(metrics._loop_lag_sampler(0.05))
    try:
        await asyncio.sleep(0.12)
        before = metrics.loop_account()
        assert isinstance(before["loop_lag_us"], int)
        assert before["loop_lag_samples"] >= 2
        time.sleep(0.3)
        await asyncio.sleep(0.12)
        after = metrics.loop_account()
    finally:
        sampler.cancel()
    assert after["loop_lag_us"] - before["loop_lag_us"] >= 200_000
    assert 1 <= after["loop_lag_samples"] - before["loop_lag_samples"] <= 4
    # /healthz's reading and the scrape's peak are as they were
    assert metrics._loop_lag_last < 0.1 <= metrics._loop_lag_peak


def test_loop_account_says_nothing_of_a_sampler_that_does_not_run():
    code = """
from pushcdn_tpu.proto import metrics
account = metrics.loop_account()
assert account["loop_lag_us"] is None is account["loop_lag_samples"]
assert account["profiler_ticks"] is None is account["profiler_tick_us"]
assert account["profiler_tick_tasks"] is None
assert account["writer_dequeues"] == account["writer_wait_us"] == 0
assert account["writer_writes"] == account["writer_write_us"] == 0
print("ok")
"""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0 and proc.stdout.strip() == "ok", \
        proc.stdout[-2000:] + proc.stderr[-2000:]


async def test_profiler_ticks_are_counted_with_the_live_tasks():
    """The task profiler says what it costs: its ticks, the time each
    walk of ``all_tasks()`` held the loop, and the tasks that were
    alive; absent until a profiler runs."""
    from pushcdn_tpu.proto import metrics
    sleepers = [asyncio.create_task(asyncio.sleep(30), name=f"sleeper-{i}")
                for i in range(7)]
    profiler = asyncio.create_task(metrics._task_profiler(0.05))
    try:
        await asyncio.sleep(0.07)
        before = metrics.loop_account()
        await asyncio.sleep(0.3)
        alive = sum(not t.done() for t in asyncio.all_tasks())
        after = metrics.loop_account()
    finally:
        profiler.cancel()
        for task in sleepers:
            task.cancel()
    ticks = after["profiler_ticks"] - before["profiler_ticks"]
    assert before["profiler_ticks"] >= 1 and 3 <= ticks <= 7
    assert after["profiler_tick_tasks"] - before["profiler_tick_tasks"] \
        == ticks * alive and alive >= 9
    assert 0 < after["profiler_tick_us"] - before["profiler_tick_us"] < 0.3e6
