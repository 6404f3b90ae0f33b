"""DevicePlane consistency tests: slot quarantine, snapshot semantics,
churn under traffic, slot-table exhaustion fallback."""

import asyncio
import contextlib
import errno
import os

import numpy as np
import pytest

from pushcdn_tpu.parallel.frames import UserSlots
from tests.test_integration import Cluster, wait_until


def test_user_slots_quarantine():
    """unmap() keeps the slot index out of circulation until free_slot()."""
    s = UserSlots(2)
    a = s.assign(b"alice")
    slot = s.unmap(b"alice")
    assert slot == a
    assert s.slot_of(b"alice") is None
    b = s.assign(b"bob")
    assert b != a  # quarantined slot NOT reused
    s.free_slot(a)
    c = s.assign(b"carol")
    assert c == a  # recycled only after explicit free


def test_user_slots_grow_keeps_bindings_order_and_quarantine():
    """grow() adds slots under the free list: bindings stay, a recycled
    low slot is still handed out before any new one, a quarantined slot
    stays out until freed, and high_water moves only with assignments."""
    s = UserSlots(4)
    slots = [s.assign(b"u%d" % i) for i in range(4)]
    assert slots == [0, 1, 2, 3] and s.full
    quarantined = s.unmap(b"u1")          # in flight: not reusable yet
    s.release(b"u2")                      # recycled: reusable now
    assert not s.full
    s.grow(8)
    s.grow(6)                             # never shrinks
    assert s.capacity == 8 and s.high_water == 4
    assert [s.slot_of(b"u0"), s.slot_of(b"u3")] == [0, 3]
    assert s.key_of(7) is None
    assert s.assign(b"v0") == 2           # the recycled slot first
    assert s.assign(b"v1") == 4           # then the new range, lowest first
    assert s.assign(b"v2") == 5 and s.high_water == 6
    s.free_slot(quarantined)
    assert s.assign(b"v3") == 1           # back in circulation only now
    assert [s.assign(b"v%d" % i) for i in (4, 5)] == [6, 7] and s.full
    assert len(s) == 8


async def test_churn_during_device_traffic():
    """Users joining/leaving while steps are in flight never lose messages
    for connected users (the snapshot-per-step design)."""
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig

    cluster = await Cluster(num_brokers=1, device_plane=DevicePlaneConfig(
        num_user_slots=32, ring_slots=64, frame_bytes=1024,
        batch_window_s=0.002, bypass_max_items=0)).start()
    try:
        stable = cluster.client(seed=500, topics=[0])
        await stable.ensure_initialized()
        received = []

        async def drain():
            while True:
                got = await stable.receive_message()
                received.append(bytes(got.message))

        drain_task = asyncio.create_task(drain())
        # churn 5 short-lived clients while the stable one receives
        for i in range(5):
            churner = cluster.client(seed=600 + i, topics=[0])
            await churner.ensure_initialized()
            await churner.send_broadcast_message([0], f"round-{i}".encode())
            await asyncio.sleep(0.02)
            churner.close()
        await wait_until(
            lambda: len([r for r in received if r.startswith(b"round-")]) == 5,
            timeout=10)
        drain_task.cancel()
        device = cluster.brokers[0].device_plane
        assert device.steps >= 1
        assert not device.disabled
        stable.close()
    finally:
        await cluster.stop()


async def test_slot_table_exhaustion_falls_back_to_host(monkeypatch):
    """More users than the table's ceiling holds: registration still
    succeeds and broadcasts take the host path (no silent misses)."""
    from pushcdn_tpu.broker import device_plane
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig

    monkeypatch.setattr(device_plane, "MAX_USER_SLOTS", 2)
    cluster = await Cluster(num_brokers=1, device_plane=DevicePlaneConfig(
        num_user_slots=2, ring_slots=16, frame_bytes=1024,
        batch_window_s=0.002)).start()
    try:
        clients = []
        for i in range(4):  # 4 users, 2 slots
            c = cluster.client(seed=700 + i, topics=[0])
            await c.ensure_initialized()
            clients.append(c)
        await wait_until(
            lambda: cluster.brokers[0].connections.num_users == 4)
        device = cluster.brokers[0].device_plane
        assert len(device._unmirrored) == 2
        assert device.table_grows == 0 and device.user_slots == 2

        # a broadcast must reach ALL FOUR users (host path because of the
        # unmirrored users)
        await clients[0].send_broadcast_message([0], b"everyone")
        for c in clients:
            got = await asyncio.wait_for(c.receive_message(), 5)
            assert bytes(got.message) == b"everyone"
        for c in clients:
            c.close()
    finally:
        await cluster.stop()


async def test_idle_bypass_routes_on_host_path():
    """Depth-1 bypass: a lone message hitting a COMPLETELY idle plane is
    host-routed immediately (no step dispatch in the latency path), while
    a burst larger than the bypass budget stages onto the device."""
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig

    cluster = await Cluster(num_brokers=1, device_plane=DevicePlaneConfig(
        num_user_slots=32, ring_slots=64, frame_bytes=1024,
        batch_window_s=0.002, bypass_max_items=2)).start()
    try:
        c = cluster.client(seed=900, topics=[0])
        await c.ensure_initialized()
        device = cluster.brokers[0].device_plane

        # idle singles: delivered via the host path, zero device steps
        for i in range(3):
            await c.send_direct_message(c.public_key, b"solo %d" % i)
            got = await asyncio.wait_for(c.receive_message(), 10)
            assert bytes(got.message) == b"solo %d" % i
        assert device.steps == 0
        assert device.messages_routed == 0

        # bursts exceed the bypass budget and ride the device; retry a
        # few bursts since the broker's reader may split one across
        # small receive batches that each fit the bypass
        expected = 3
        for _ in range(5):
            await asyncio.gather(*(
                c.send_direct_message(c.public_key, b"burst %d" % i)
                for i in range(16)))
            got = 0
            async with asyncio.timeout(20):
                while got < 16:
                    got += len(await c.receive_messages(16 - got))
            if device.messages_routed > 0:
                break
        assert device.messages_routed > 0
        c.close()
    finally:
        await cluster.stop()


def test_pump_common_helpers():
    """The shared pump machinery (broker/pump_common.py) both planes use."""
    from pushcdn_tpu.broker.pump_common import (
        CoalesceGate, RevCache, effective_users)

    # user-table slice mark: the power of two that holds the high-water
    # mark, clamped, never under 64
    assert effective_users(0, 1024) == 64
    assert effective_users(1, 1024) == 64
    assert effective_users(64, 1024) == 64
    assert effective_users(65, 1024) == 128
    assert effective_users(129, 1024) == 256
    assert effective_users(1000, 1024) == 1024
    assert effective_users(5000, 1024) == 1024
    assert effective_users(10, 32) == 32  # capacity below the least mark
    # a table that doubles when full crosses a mark exactly when it grows
    assert effective_users(1024, 1024) == 1024
    assert effective_users(1025, 2048) == 2048
    assert effective_users(4096, 4096) == 4096
    assert effective_users(5000, 8192) == 8192

    # coalescing gate: burst-after-idle and saturation step immediately,
    # a recent-step trickle waits one window
    g = CoalesceGate(batch_window_s=0.001, coalesce_min_frames=16)
    assert g.wait_s(1, now=100.0) == 0          # idle: no window
    g.stepped(100.0)
    assert g.wait_s(1, now=100.001) == 0.001    # trickle: coalesce
    assert g.wait_s(16, now=100.001) == 0       # saturated: step now
    assert g.wait_s(0, now=100.001) == 0        # nothing staged
    assert g.wait_s(1, now=100.5) == 0          # idle again

    # revision cache: builds once per revision; None never caches
    cache = RevCache()
    calls = []
    assert cache.get(1, lambda: calls.append(1) or "a") == "a"
    assert cache.get(1, lambda: calls.append(2) or "b") == "a"
    assert cache.get(2, lambda: calls.append(3) or "c") == "c"
    assert calls == [1, 3]
    assert cache.get(None, lambda: calls.append(4) or "w") == "w"
    assert cache.get(2, lambda: calls.append(5) or "x") == "c"


async def test_device_plane_fail_open_to_host_path():
    """A failing device step must not lose acked frames: the staged batch
    re-routes over the host path, the plane disables itself, and the
    broker keeps serving as a plain host broker (fail-open, matching the
    reference's any-core-failure posture)."""
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig

    cluster = await Cluster(num_brokers=1, device_plane=DevicePlaneConfig(
        num_user_slots=32, ring_slots=64, frame_bytes=1024,
        batch_window_s=0.002, bypass_max_items=0)).start()
    try:
        a = cluster.client(seed=1100, topics=[0])
        b = cluster.client(seed=1101, topics=[0])
        await a.ensure_initialized()
        await b.ensure_initialized()
        device = cluster.brokers[0].device_plane

        # sanity: the plane routes before the failure
        await a.send_broadcast_message([0], b"pre-failure")
        for c in (a, b):
            got = await asyncio.wait_for(c.receive_message(), 10)
            assert bytes(got.message) == b"pre-failure"

        # break the step underneath the pump
        def boom(*args, **kwargs):
            raise RuntimeError("injected device failure")
        device._run_step = boom

        await a.send_broadcast_message([0], b"survives the failure")
        for c in (a, b):
            got = await asyncio.wait_for(c.receive_message(), 10)
            assert bytes(got.message) == b"survives the failure"

        await wait_until(lambda: device.disabled)
        # the broker is now a plain host broker; traffic still flows
        await b.send_direct_message(a.public_key, b"host path onward")
        got = await asyncio.wait_for(a.receive_message(), 10)
        assert bytes(got.message) == b"host path onward"
        a.close()
        b.close()
    finally:
        await cluster.stop()


async def test_ragged_delivery_impl_end_to_end():
    """delivery_impl="ragged": the plane routes through the paged walk
    (compact pairs feed egress directly) and delivers byte-identically —
    broadcasts, a multi-topic union (deduped to one copy), and directs."""
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig

    cluster = await Cluster(num_brokers=1, device_plane=DevicePlaneConfig(
        num_user_slots=32, ring_slots=64, frame_bytes=1024,
        batch_window_s=0.002, bypass_max_items=0,
        delivery_impl="ragged")).start()
    try:
        device = cluster.brokers[0].device_plane
        assert device.delivery_impl == "ragged"
        stable = cluster.client(seed=520, topics=[0, 1])
        await stable.ensure_initialized()
        received = []

        async def drain():
            while True:
                got = await stable.receive_message()
                received.append(bytes(got.message))

        drain_task = asyncio.create_task(drain())
        sender = cluster.client(seed=521, topics=[])
        await sender.ensure_initialized()
        for i in range(4):
            await sender.send_broadcast_message([0], b"m%d" % i)
        await sender.send_broadcast_message([1], b"t1")
        await sender.send_broadcast_message([0, 1], b"union")  # dedup
        await sender.send_direct_message(stable.public_key, b"direct")
        await wait_until(lambda: len(received) >= 7, timeout=10)
        await asyncio.sleep(0.05)  # a dup would land right behind
        drain_task.cancel()
        assert sorted(received) == sorted(
            [b"m0", b"m1", b"m2", b"m3", b"t1", b"union", b"direct"])
        assert device.ragged_steps >= 1
        assert not device.disabled
        stable.close()
        sender.close()
    finally:
        await cluster.stop()


async def test_ragged_page_pool_exhaustion_falls_back_then_recovers():
    """A too-small page pool: the plane flips to the dense step (never a
    dropped delivery), keeps serving, and once membership shrinks the
    rebuild-retry path restores the paged walk."""
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig

    cluster = await Cluster(num_brokers=1, device_plane=DevicePlaneConfig(
        num_user_slots=32, ring_slots=64, frame_bytes=1024,
        batch_window_s=0.002, bypass_max_items=0,
        delivery_impl="ragged", ragged_max_pages=2)).start()
    try:
        device = cluster.brokers[0].device_plane
        # two subscribers on different topics exhaust the 1-usable-page
        # pool (page 0 reserved): the second add overflows
        a = cluster.client(seed=530, topics=[0])
        await a.ensure_initialized()
        b = cluster.client(seed=531, topics=[1])
        await b.ensure_initialized()
        await wait_until(lambda: device.delivery_impl == "dense",
                         timeout=5)
        received = []

        async def drain():
            while True:
                got = await a.receive_message()
                received.append(bytes(got.message))

        drain_task = asyncio.create_task(drain())
        sender = cluster.client(seed=532, topics=[])
        await sender.ensure_initialized()
        await sender.send_broadcast_message([0], b"after-fallback")
        await wait_until(lambda: received == [b"after-fallback"],
                         timeout=10)
        assert not device.disabled
        # membership shrinks below the retry mark: the removal's own
        # observer call rebuilds the index and resumes the paged walk
        b.close()
        await wait_until(lambda: device.delivery_impl == "ragged",
                         timeout=10)
        await sender.send_broadcast_message([0], b"after-recovery")
        await wait_until(
            lambda: received == [b"after-fallback", b"after-recovery"],
            timeout=10)
        drain_task.cancel()
        assert not device.disabled
        for c in (a, sender):
            c.close()
    finally:
        await cluster.stop()


# ---------------------------------------------------------------------------
# served differential: the device plane's egress (idle links written by the
# pump, the rest by their writers) against the plain host router, over real
# TCP links, one of them to a reader that stalls
# ---------------------------------------------------------------------------

_N_USERS, _PUBLISHERS, _FRAMES, _SLOW = 6, 3, 150, 5


def _mixed_traffic(seed: int):
    """Per publisher its frames in order, ``(kind, target, payload)``, and
    per user what it is owed: ``{(publisher, stream): [seq, ...]}``. User
    ``u`` subscribes to topic ``u % 2``, the slow reader to both."""
    import random
    rng = random.Random(seed)
    topics = [{u % 2} for u in range(_N_USERS)]
    topics[_SLOW] = {0, 1}
    plan = [[] for _ in range(_PUBLISHERS)]
    owed = [{} for _ in range(_N_USERS)]
    for p in range(_PUBLISHERS):
        seqs = {}
        for _ in range(_FRAMES):
            if rng.random() < 0.75:
                kind, target = "broadcast", rng.randrange(2)
                stream, to = "t%d" % target, [
                    u for u in range(_N_USERS) if target in topics[u]]
            else:
                kind, target = "direct", rng.randrange(_N_USERS)
                stream, to = "direct", [target]
            seq = seqs[stream] = seqs.get(stream, -1) + 1
            payload = (b"%d|%s|%d|" % (p, stream.encode(), seq)).ljust(
                rng.choice((64, 600, 900)), b".")
            plan[p].append((kind, target, payload))
            for u in to:
                owed[u].setdefault((p, stream), []).append(seq)
    return topics, plan, owed


@contextlib.asynccontextmanager
async def _served_over_tcp(seed: int, device_plane, topics, protocol=None,
                           topic_space=None):
    """One broker (with ``device_plane``, or the plain host router) and a
    marshal, with one connected client per entry of ``topics`` over real
    TCP user links (or ``protocol``'s): ``(broker, clients)``. The topics
    are the testing run definition's two unless ``topic_space`` says."""
    import tempfile

    from pushcdn_tpu.bin.common import free_ports
    from pushcdn_tpu.broker.broker import Broker, BrokerConfig
    from pushcdn_tpu.broker.tasks.heartbeat import heartbeat_once
    from pushcdn_tpu.client import Client, ClientConfig
    from pushcdn_tpu.marshal import Marshal, MarshalConfig
    from pushcdn_tpu.proto.crypto.signature import DEFAULT_SCHEME
    from pushcdn_tpu.proto.def_ import testing_run_def
    from pushcdn_tpu.proto.transport import Tcp

    protocol = protocol or Tcp
    run_def = testing_run_def(user_protocol=protocol, topics=topic_space)
    db = os.path.join(tempfile.mkdtemp(prefix="pushcdn-diff-"), "d.sqlite")
    pub, marshal_port = free_ports(2)
    tag = f"diff-{seed}-{'dev' if device_plane else 'host'}"
    broker = await Broker.new(BrokerConfig(
        run_def=run_def, keypair=DEFAULT_SCHEME.generate_keypair(seed=seed),
        discovery_endpoint=db,
        public_advertise_endpoint=f"127.0.0.1:{pub}",
        public_bind_endpoint=f"127.0.0.1:{pub}",
        private_advertise_endpoint=f"{tag}-priv",
        private_bind_endpoint=f"{tag}-priv",
        heartbeat_interval_s=3600, sync_interval_s=3600,
        whitelist_interval_s=3600, device_plane=device_plane))
    await broker.start()
    await heartbeat_once(broker)
    marshal = await Marshal.new(MarshalConfig(
        run_def=run_def, discovery_endpoint=db,
        bind_endpoint=f"127.0.0.1:{marshal_port}"))
    await marshal.start()
    clients = [Client(ClientConfig(
        marshal_endpoint=f"127.0.0.1:{marshal_port}",
        keypair=DEFAULT_SCHEME.generate_keypair(seed=seed + 1 + u),
        protocol=protocol, subscribed_topics=set(topics[u])))
        for u in range(len(topics))]
    try:
        for c in clients:
            await c.ensure_initialized()
        await wait_until(
            lambda: broker.connections.num_users == len(clients))
        yield broker, clients
    finally:
        for c in clients:
            c.close()
        await marshal.stop()
        await broker.stop()


async def _serve_mixed_traffic(seed: int, device_plane):
    """Run the seeded traffic through one broker with real TCP user links;
    returns what each user received, what it was owed, and the plane (or
    None)."""
    import socket

    from pushcdn_tpu.proto import ledger as ledger_mod

    ledger_mod.reset_for_tests()
    topics, plan, owed = _mixed_traffic(seed)
    got = [{} for _ in range(_N_USERS)]
    counts = [0] * _N_USERS

    async def drain(u):
        while True:
            for m in await clients[u].receive_messages():
                p, stream, seq, _ = bytes(m.message).split(b"|", 3)
                got[u].setdefault((int(p), stream.decode()), []).append(
                    int(seq))
                counts[u] += 1

    async def publish(p):
        for kind, target, payload in plan[p]:
            if kind == "broadcast":
                await clients[p].send_broadcast_message([target], payload)
            else:
                await clients[p].send_direct_message(
                    clients[target].public_key, payload)

    drains = []
    async with _served_over_tcp(seed, device_plane, topics) as (broker,
                                                                clients):
        try:
            # the slow reader: it stops reading, behind socket buffers
            # small enough that its link backs up within the traffic
            slow = clients[_SLOW]._connection._stream
            slow.writer.get_extra_info("socket").setsockopt(
                socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
            broker.connections.get_user_connection(
                clients[_SLOW].public_key) \
                ._stream.writer.get_extra_info("socket").setsockopt(
                    socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
            slow.reader._transport.pause_reading()
            drains = [asyncio.create_task(drain(u))
                      for u in range(_N_USERS)]
            await asyncio.gather(*(publish(p) for p in range(_PUBLISHERS)))
            fast = [u for u in range(_N_USERS) if u != _SLOW]
            await wait_until(lambda: all(
                counts[u] == sum(map(len, owed[u].values())) for u in fast),
                timeout=30)
            slow.reader._transport.resume_reading()  # well inside the timeout
            await wait_until(
                lambda: counts[_SLOW] == sum(map(len, owed[_SLOW].values())),
                timeout=30)
            assert broker.connections.num_users == _N_USERS  # nobody removed
            # the frame-fate ledger closes over every way a stream left:
            # batched, written by the pump one by one, or queued
            book = ledger_mod.LEDGER
            await wait_until(lambda: book.walk_live_queues() == 0)
            assert book.derived_in_queue() == [0] * len(book.queued), \
                (book.queued, book.fates)
            delivered = sum(book.fates[("delivered", "egress")])
            assert delivered >= sum(sum(map(len, o.values())) for o in owed)
        finally:
            for t in drains:
                t.cancel()
    return got, owed, broker.device_plane


@pytest.mark.parametrize("seed, ring_slots", [(2601, 64), (2602, 64),
                                              (2603, 16)])
async def test_served_egress_matches_the_host_router_with_a_slow_reader(
        seed, ring_slots, monkeypatch):
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from pushcdn_tpu.broker.tasks import senders

    handoffs = []  # every one-by-one stream hand-off that did not fail
    real = senders.try_send_encoded_to_user_nowait

    def counted(*args, **kwargs):
        how = real(*args, **kwargs)
        if how:
            handoffs.append(how)
        return how
    monkeypatch.setattr(senders, "try_send_encoded_to_user_nowait", counted)

    by_host, owed, no_plane = await _serve_mixed_traffic(seed, None)
    assert no_plane is None and not handoffs
    by_device, _, plane = await _serve_mixed_traffic(
        seed, DevicePlaneConfig(
            num_user_slots=32, ring_slots=ring_slots, frame_bytes=1024,
            batch_window_s=0.002, bypass_max_items=0))
    # every user, every (publisher, stream): the same sequence both ways,
    # and it is the publisher's own order with nothing lost or repeated
    assert by_device == by_host == owed
    assert not plane.disabled and plane.steps >= 1
    # idle links were written by the pump, the stalled one by its writer;
    # the sends of a step whose take found the base lane full (three
    # publishers fill 16 slots at every take) left in one native batch,
    # the stalled link's short send among them; and the tallies are all
    # the hand-offs there were
    assert plane.egress_inline > 0 and plane.egress_queued > 0
    assert plane.egress_batched <= plane.egress_inline
    if ring_slots == 16:
        assert plane.egress_batched > 0
    assert plane.egress_inline + plane.egress_queued == \
        len(handoffs) + plane.egress_batched
    described = plane.describe()
    assert (described["egress_inline"], described["egress_queued"],
            described["egress_batched"]) == \
        (plane.egress_inline, plane.egress_queued, plane.egress_batched)


# ---------------------------------------------------------------------------
# the pump's drain (ISSUE 28): after a ``plane.egress`` in which the pump
# wrote streams itself the loop has stood still, so what publishers sent
# meanwhile sits in their sockets. It is staged before the next take and
# rides that step, and the plane does not count as idle meanwhile.
# ---------------------------------------------------------------------------

_SMALL_PLANE = dict(num_user_slots=32, ring_slots=64, frame_bytes=1024,
                    batch_window_s=0.002)


def _wire(*payloads: bytes, topic: int = 0, to: bytes = None) -> bytes:
    """Broadcasts on ``topic``, or directs to the user ``to``, as a
    publisher's kernel holds them: each frame behind the transport's u32
    length."""
    import struct

    from pushcdn_tpu.proto.message import Broadcast, Direct, serialize
    frames = [serialize(Broadcast(topics=[topic], message=p) if to is None
                        else Direct(recipient=to, message=p))
              for p in payloads]
    return b"".join(struct.pack(">I", len(f)) + f for f in frames)


def _socket_of(client) -> int:
    """The file descriptor of a client's TCP socket (asyncio's own handle
    on it has no ``send``)."""
    return client._connection._stream.writer.get_extra_info(
        "socket").fileno()


def _write_during_egress(monkeypatch, sock: int, armed: list):
    """While ``armed`` holds wire bytes, the pump's next hand-off of a
    stream first writes them into ``sock``: a remote publisher's frames
    reaching the broker's socket while ``plane.egress`` holds the loop
    (clients of this process share that loop and cannot write then), and
    that egress holds it past the coalescing gate's memory of a step (4 x
    ``batch_window_s``), as the ``send()``s of a wide fan-out do."""
    import time

    from pushcdn_tpu.broker.tasks import senders
    real = senders.try_send_encoded_to_user_nowait

    def hand_off(*args, **kwargs):
        if armed:
            os.write(sock, armed.pop())
            time.sleep(5 * _SMALL_PLANE["batch_window_s"])
        return real(*args, **kwargs)
    monkeypatch.setattr(senders, "try_send_encoded_to_user_nowait", hand_off)


def _record_takes(plane, in_worker=lambda n_taken: None) -> list:
    """Per step, the payloads its take held, in ring order. ``in_worker``
    runs on the worker thread at the end of each step's device phase."""
    from pushcdn_tpu.proto.message import deserialize
    taken = []
    real = plane._run_step

    def run_step(batches, *args, **kwargs):
        if kwargs.get("compile_only"):
            return real(batches, *args, **kwargs)
        taken.append([
            bytes(deserialize(b.bytes_[i, :b.length[i]].tobytes()).message)
            for b in batches for i in range(len(b.valid)) if b.valid[i]])
        jobs = real(batches, *args, **kwargs)
        in_worker(len(taken))
        return jobs
    plane._run_step = run_step
    return taken


async def _receive_all(clients, n: int) -> list:
    """What each client received once each has ``n`` messages, and for a
    moment after (a duplicate would land right behind)."""
    got = [[] for _ in clients]

    async def read(u):
        while True:
            for m in await clients[u].receive_messages():
                got[u].append(bytes(m.message))
    readers = [asyncio.create_task(read(u)) for u in range(len(clients))]
    try:
        await wait_until(lambda: all(len(g) >= n for g in got), timeout=20)
        await asyncio.sleep(0.05)
    finally:
        for t in readers:
            t.cancel()
    return got


async def test_frames_that_came_during_an_inline_egress_ride_the_next_step(
        monkeypatch):
    """Step N's worker phase stages one frame (so the pump does not park
    after it), and five more reach the publisher's socket while step N's
    egress holds the loop: they are in the take of step N+1, not N+2, and
    every subscriber gets the publisher's order, each frame once."""
    import threading

    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig

    during = [b"during %d" % i for i in range(5)]
    async with _served_over_tcp(
            2801, DevicePlaneConfig(bypass_max_items=0, **_SMALL_PLANE),
            [{0}] * 4) as (broker, clients):
        plane = broker.device_plane
        publisher = clients[0]
        armed = [_wire(*during)]
        _write_during_egress(monkeypatch, _socket_of(publisher), armed)
        in_worker, go = threading.Event(), threading.Event()

        def hold_step_0(n_taken):   # until "mid" is staged
            if n_taken == 1:
                in_worker.set()
                go.wait(10)
        taken = _record_takes(plane, hold_step_0)

        await publisher.send_broadcast_message([0], b"first")
        await wait_until(in_worker.is_set)
        await publisher.send_broadcast_message([0], b"mid")
        await wait_until(lambda: plane.frames_staged == 2)
        go.set()
        got = await _receive_all(clients, 7)

        assert not armed and plane.egress_inline > 0
        assert taken[:2] == [[b"first"], [b"mid"] + during], taken
        assert got == [[b"first", b"mid"] + during] * 4
        assert plane.frames_staged == 7 and plane.frames_drained == 5
        assert plane.describe()["frames_drained"] == 5
        assert not plane.disabled and not plane._between_steps


@pytest.mark.parametrize("when", ["draining", "parked"])
async def test_the_plane_is_idle_only_while_the_pump_is_parked(
        when, monkeypatch):
    """A lone frame (within ``bypass_max_items``) on empty rings with no
    step in flight: between an egress the pump wrote itself and its next
    take the plane is not idle and the frame is STAGED; with the pump
    parked it is idle and the frame is host-routed (INELIGIBLE), the
    contract ``echo-sparse`` runs on."""
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from pushcdn_tpu.broker.staging import StageResult

    async with _served_over_tcp(
            2810, DevicePlaneConfig(bypass_max_items=2, **_SMALL_PLANE),
            [{0}] * 3) as (broker, clients):
        plane = broker.device_plane
        sock = _socket_of(clients[0])
        armed = [_wire(b"lone")] if when == "draining" else []
        _write_during_egress(monkeypatch, sock, armed)
        # (frames, was the plane idle, results) per batch the scalar
        # scan staged, and per chunk the native pass took frames of
        seen = []
        real, real_chunk = plane.stage_batch, plane.stage_chunk

        def stage_batch(items):
            idle = plane._idle_bypass(len(items))
            results = real(items)
            seen.append((len(items), idle, results))
            return results

        def stage_chunk(buf, offs, lens, first):
            idle = plane._idle_bypass(len(offs) - first)
            taken, status, counts = real_chunk(buf, offs, lens, first)
            if taken:
                seen.append((taken, idle, [
                    StageResult.STAGED if s & 1 else StageResult.FULL
                    for s in status]))
            return taken, status, counts
        plane.stage_batch, plane.stage_chunk = stage_batch, stage_chunk

        burst = [b"burst %d" % i for i in range(4)]
        os.write(sock, _wire(*burst))   # one read, one batch over the bypass
        if when == "parked":
            await wait_until(lambda: plane.messages_routed == 12)
            await asyncio.sleep(0.05)   # the drain is over, the pump parked
            assert not plane._between_steps and not plane._step_inflight
            os.write(sock, _wire(b"lone"))
        got = await _receive_all(clients, 5)

        assert got == [burst + [b"lone"]] * 3 and not armed
        assert plane.egress_inline > 0
        assert seen[0] == (4, False, [StageResult.STAGED] * 4)
        if when == "draining":
            assert seen[1:] == [(1, False, [StageResult.STAGED])]
            assert (plane.frames_staged, plane.frames_drained,
                    plane.steps) == (5, 1, 2)
        else:
            assert seen[1:] == [(1, True, [StageResult.INELIGIBLE])]
            assert (plane.frames_staged, plane.frames_drained,
                    plane.steps) == (4, 0, 1)


async def test_the_drain_is_skipped_after_an_all_queued_egress():
    """Streams that all went to their writers did not hold the loop (the
    Memory transport has no ``write_nowait``): the pump goes straight back
    to ``_kick.wait()``."""
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig

    cluster = await Cluster(num_brokers=1, device_plane=DevicePlaneConfig(
        bypass_max_items=0, **_SMALL_PLANE)).start()
    try:
        plane = cluster.brokers[0].device_plane
        drains = []
        real = plane._drain

        async def drain():
            drains.append(plane.steps)
            return await real()
        plane._drain = drain
        c = cluster.client(seed=2820, topics=[0])
        await c.ensure_initialized()
        for round_ in range(3):
            await asyncio.gather(*(
                c.send_direct_message(c.public_key, b"%d %d" % (round_, i))
                for i in range(8)))
            got = 0
            async with asyncio.timeout(20):
                while got < 8:
                    got += len(await c.receive_messages(8 - got))
        assert plane.steps >= 3 and plane.egress_queued >= 3
        assert plane.egress_inline == 0 and not drains
        assert plane.frames_drained == 0 and not plane._between_steps
        c.close()
    finally:
        await cluster.stop()


@pytest.mark.parametrize("sockets", ["quiet", "endless", "full_lane"])
async def test_the_drain_is_bounded_in_loop_passes(sockets):
    """The chain's length in loop passes whether nothing comes (quiet) or a
    publisher never stops writing (endless: a frame staged every pass),
    and no pass at all on a full base lane (its stagers are
    back-pressured), though the wide lane has room."""
    from pushcdn_tpu.broker import device_plane
    from pushcdn_tpu.broker.device_plane import DevicePlane, DevicePlaneConfig
    from pushcdn_tpu.broker.staging import StageResult
    from pushcdn_tpu.proto.limiter import Bytes
    from pushcdn_tpu.proto.message import Broadcast, serialize

    plane = DevicePlane(None, DevicePlaneConfig(
        num_user_slots=32, ring_slots=16, frame_bytes=1024,
        extra_lanes=((4096, 4),), bypass_max_items=0))
    message = Broadcast(topics=[0], message=b"x")
    frame = serialize(message)
    passes = 0

    def stage():
        return plane.try_stage(message, Bytes(frame))

    async def each_pass():
        nonlocal passes
        while True:
            passes += 1
            if sockets == "endless":
                stage()
            await asyncio.sleep(0)

    if sockets == "full_lane":
        while plane.rings[0].free_slots:
            assert stage() == StageResult.STAGED
        assert plane.rings[1].free_slots
    ticker = asyncio.create_task(each_pass())
    try:
        await asyncio.sleep(0)   # the ticker is running
        plane._between_steps = True
        assert not plane._idle_bypass(1)
        before = passes
        drained = await asyncio.wait_for(plane._drain(), 10)
        spent = passes - before
    finally:
        ticker.cancel()
    assert spent == (0 if sockets == "full_lane"
                     else device_plane._DRAIN_PASSES)
    assert drained == (spent if sockets == "endless" else 0)
    assert not plane._between_steps


# ---------------------------------------------------------------------------
# the CPU pacer: off saturation, a step whose sends are the
# period sends in the native batch, and the take after it waits until the
# wall has caught up with the CPU the step cost
# ---------------------------------------------------------------------------

_MS = 1_000_000


@pytest.mark.parametrize(
    "device_ms,egress_wall_ms,egress_cpu_ms,back_pressured,leads", [
        (30, 400, 400, False, True),    # one by one: the sends, the period
        (30, 80, 400, False, True),     # batched: the CPU still says so
        (120, 40, 40, False, False),    # the worker is the period
        (30, 400, 400, True, False),    # back-pressured: its rate's length
    ], ids=["one_by_one", "batched", "worker_leads", "back_pressured"])
def test_the_pacer_observes_the_sends_and_owes_the_cpu(
        device_ms, egress_wall_ms, egress_cpu_ms, back_pressured, leads):
    """On injected clocks: ``sends_lead`` is the egress's CPU against the
    wall from the take to the egress, and what the next take owes is the
    CPU since the take less the wall since it."""
    from pushcdn_tpu.broker.pump_common import CpuPacer

    cpu, wall = [5 * _MS], [7 * _MS]
    pacer = CpuPacer(cpu_ns=lambda: cpu[0], wall_ns=lambda: wall[0])
    assert not pacer.sends_lead and pacer.owed_ns() == 0
    pacer.took()
    wall[0] += device_ms * _MS
    cpu[0] += device_ms * _MS   # the worker's thread
    pacer.egress_began()
    wall[0] += egress_wall_ms * _MS
    cpu[0] += egress_cpu_ms * _MS
    pacer.egress_ended(back_pressured)
    assert pacer.sends_lead == leads
    assert pacer.owed_ns() == (egress_cpu_ms - egress_wall_ms) * _MS
    wall[0] += 10 * _MS         # a wait, at no CPU, is paid off
    owed = (egress_cpu_ms - egress_wall_ms - 10) * _MS
    assert pacer.owed_ns() == owed
    # a take after a paced step carries what is still owed, never a credit
    pacer.took(carry=True)
    assert pacer.owed_ns() == max(owed, 0)
    pacer.took()
    assert pacer.owed_ns() == 0


@pytest.mark.parametrize("case", ["paced", "owes_nothing", "lane_full",
                                  "lane_fills_meanwhile"])
async def test_a_paced_take_waits_the_cpu_and_a_back_pressured_one_never(
        case):
    """``_pace`` on a plane whose CPU clock is injected: a take that owes
    60 ms of CPU waits until the wall has caught up with it, and the
    account says how long (``pump_paced_us`` / ``pump_paced_steps``); a
    take that owes nothing, or that finds the base lane full, does not
    wait; a stager that fills the lane ends the wait."""
    import time

    from pushcdn_tpu.broker.device_plane import DevicePlane, DevicePlaneConfig
    from pushcdn_tpu.broker.staging import StageResult
    from pushcdn_tpu.proto.limiter import Bytes
    from pushcdn_tpu.proto.message import Broadcast, serialize

    plane = DevicePlane(None, DevicePlaneConfig(
        num_user_slots=32, ring_slots=16, frame_bytes=1024,
        bypass_max_items=0))
    message = Broadcast(topics=[0], message=b"x")
    frame = serialize(message)

    def fill():
        while plane.rings[0].free_slots:
            assert plane.try_stage(message, Bytes(frame)) == \
                StageResult.STAGED

    async def fill_later():
        await asyncio.sleep(0.02)
        fill()

    cpu = [0]
    pacer = plane._pacer
    pacer.cpu_ns = lambda: cpu[0]
    owes = {"paced": 60, "owes_nothing": 0}.get(case, 5000) * _MS
    if case == "lane_full":
        fill()
    filler = asyncio.create_task(fill_later()) \
        if case == "lane_fills_meanwhile" else None
    pacer.took()
    cpu[0] += owes
    t0 = time.monotonic_ns()
    waited = await plane._pace()
    elapsed = time.monotonic_ns() - t0
    if filler is not None:
        await filler
    counters = plane._account.counters()
    assert 0 <= waited <= elapsed
    assert counters["pump_paced_us"] == waited // 1000
    assert counters["pump_paced_steps"] == (waited > 0)
    if case == "paced":
        assert pacer.owed_ns() <= 0 < owes - 10 * _MS < waited
        assert elapsed < owes + 500 * _MS
    elif case == "lane_fills_meanwhile":
        assert 10 * _MS < waited < elapsed < 2000 * _MS
        assert not plane.rings[0].free_slots
    else:
        assert waited == 0 and elapsed < 50 * _MS


# ---------------------------------------------------------------------------
# the native batch: the sends of a step whose take found the base lane full,
# or whose sends are the period off saturation, leave in one
# ``native.send_batch`` call, for the links that are idle plain sockets;
# every other step, and every other link, goes one by one.
# ---------------------------------------------------------------------------

_LANE = 16
_BATCH_PLANE = dict(num_user_slots=32, ring_slots=_LANE, frame_bytes=1024,
                    batch_window_s=0.002, bypass_max_items=0)


def _record_batches(monkeypatch, before=lambda fds: None,
                    after=lambda fds, sent: sent) -> list:
    """Every ``native.send_batch`` call as ``(fds, nbytes, sent)`` lists;
    ``before`` runs just ahead of the real call, ``after`` may rewrite
    what it returned."""
    from pushcdn_tpu import native
    calls = []
    real = native.send_batch

    def send_batch(buf, fds, offsets, nbytes):
        before(fds.tolist())
        sent = after(fds.tolist(), real(buf, fds, offsets, nbytes))
        calls.append((fds.tolist(), nbytes.tolist(), sent.tolist()))
        return sent
    monkeypatch.setattr(native, "send_batch", send_batch)
    return calls


def _broker_fd(broker, client) -> int:
    """The broker's end of ``client``'s TCP link."""
    return broker.connections.get_user_connection(client.public_key) \
        ._stream.writer.get_extra_info("socket").fileno()


def _costly_sends(monkeypatch, plane) -> list:
    """The pump observes that a step's sends are its period: each
    hand-off one by one, and each native batch (where ``_record_batches``
    is handed the returned list's ``cost`` as its ``before``), moves the
    pacer's CPU and wall clocks on by a second together, as a send that
    held the loop's core for that second would. A pace waits their
    difference, so these seconds add nothing to it; the list's one
    element is the CPU the test adds alone (``pacer_cpu_ns``)."""
    import time

    from pushcdn_tpu.broker.tasks import senders
    ahead = [0, 0]   # both clocks; the CPU clock alone
    pacer = plane._pacer
    pacer.cpu_ns = lambda: time.process_time_ns() + ahead[0] + ahead[1]
    pacer.wall_ns = lambda: time.monotonic_ns() + ahead[0]

    def cost(*_):
        ahead[0] += 10**9
    real = senders.try_send_encoded_to_user_nowait

    def hand_off(*args, **kwargs):
        cost()
        return real(*args, **kwargs)
    monkeypatch.setattr(senders, "try_send_encoded_to_user_nowait", hand_off)
    return [cost, ahead]


@pytest.mark.parametrize("frames,sends_lead", [
    (_LANE, False), (_LANE - 1, False), (_LANE - 1, True)],
    ids=["lane_full", "lane_not_full", "lane_not_full_sends_lead"])
async def test_only_a_back_pressured_step_is_sent_by_the_native_batch(
        frames, sends_lead, monkeypatch):
    """Which step's sends leave in the native batch: one whose take found
    the base lane full; one whose take found room, where the step before
    it observed that its sends were the period (``CpuPacer``); no other.
    A first step (here one short of the lane) goes one by one and makes
    the observation; the second is the one judged."""
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig

    first = [b"first %d" % i for i in range(_LANE - 1)]
    payloads = [b"frame %d" % i for i in range(frames)]
    async with _served_over_tcp(
            3101, DevicePlaneConfig(**_BATCH_PLANE), [{0}] * 4) as (
                broker, clients):
        plane = broker.device_plane
        if sends_lead:
            _costly_sends(monkeypatch, plane)
        os.write(_socket_of(clients[0]), _wire(*first))
        assert await _receive_all(clients, len(first)) == [first] * 4
        assert (plane.steps, plane.egress_batched) == (1, 0)
        assert plane._pacer.sends_lead == sends_lead
        calls = _record_batches(monkeypatch)
        # one write, one read, one receive batch: the take finds what it
        # staged, all 16 slots or one short of them
        os.write(_socket_of(clients[0]), _wire(*payloads))
        got = await _receive_all(clients, frames)
        assert got == [payloads] * 4
        assert (plane.steps, plane.egress_inline, plane.egress_queued) == \
            (2, 8, 0)
        if frames == _LANE or sends_lead:
            assert plane.egress_batched == 4
            (fds, nbytes, sent), = calls
            assert sorted(fds) == sorted(_broker_fd(broker, c)
                                         for c in clients)
            assert sent == nbytes
        else:
            assert plane.egress_batched == 0 and not calls
        described = plane.describe()
        assert described["egress_batched"] == plane.egress_batched
        assert described["egress_offsat_batched"] == (4 if sends_lead else 0)
        if frames == _LANE:
            assert described["pump_paced_steps"] == 0


@pytest.mark.parametrize("regime", ["saturated", "off_saturation"])
async def test_a_short_send_keeps_its_order_and_later_steps_queue_behind_it(
        regime, monkeypatch):
    """A reader that stops reading: the batch's ``send()`` takes part of
    its stream, the transport gets the rest, and while it holds bytes the
    link is not batched again (its later streams join the transport's
    buffer, then the writer's queue); the others go on in the batch.
    Saturated, every take finds the base lane full; off saturation it
    finds room, and the steps are batched because their sends are the
    period (after a first step one by one, before the reader stops) and
    the takes after them paced."""
    import socket

    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig

    # 48 KB a user a step: under one flush unit, over what the stalled
    # link's socket buffers take
    lane = 48
    rounds = [[(b"%d.%d|" % (r, i)).ljust(1000, b".") for i in range(lane)]
              for r in range(5)]
    off = regime == "off_saturation"
    prime = [b"prime %d" % i for i in range(lane)] if off else []
    async with _served_over_tcp(
            3110, DevicePlaneConfig(**dict(
                _BATCH_PLANE, ring_slots=2 * lane if off else lane)),
            [{0}] * 3) as (broker, clients):
        plane = broker.device_plane
        if off:
            cost, _ = _costly_sends(monkeypatch, plane)
            os.write(_socket_of(clients[0]), _wire(*prime))
            assert await _receive_all(clients, lane) == [prime] * 3
            assert plane._pacer.sends_lead and not plane.egress_batched
            calls = _record_batches(monkeypatch, before=cost)
        else:
            calls = _record_batches(monkeypatch)
        stalled = clients[2]
        link = broker.connections.get_user_connection(stalled.public_key)
        stream = stalled._connection._stream
        stream.writer.get_extra_info("socket").setsockopt(
            socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        link._stream.writer.get_extra_info("socket").setsockopt(
            socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        stream.reader._transport.pause_reading()
        fd = _broker_fd(broker, stalled)
        transport = link._stream.writer.transport
        short = unbatched = 0
        for r, frames in enumerate(rounds):
            held = transport.get_write_buffer_size()
            assert (link.idle_fd(1) is None) == bool(held)
            os.write(_socket_of(clients[0]), _wire(*frames))
            await wait_until(lambda: plane.steps == r + 1 + off
                             and not plane._step_inflight)
            assert await _receive_all(clients[:2], lane) == [frames] * 2
            fds, nbytes, sent = calls[r]
            if held:    # bytes on the fd would pass the transport's
                assert fd not in fds and len(fds) == 2
                unbatched += 1
            else:
                at = fds.index(fd)
                short += sent[at] < nbytes[at]
                del nbytes[at], sent[at]
            assert sent == nbytes       # the readers that read
        # the socket filled, then the transport's buffer passed its
        # low-water mark, then the writer's queue took the streams
        assert short and unbatched and plane.egress_queued >= 1
        assert plane.egress_batched == sum(len(c[0]) for c in calls)
        assert plane.egress_inline + plane.egress_queued == \
            3 * (len(rounds) + off)
        described = plane.describe()
        assert described["egress_offsat_batched"] == \
            (plane.egress_batched if off else 0)
        if not off:
            assert described["pump_paced_steps"] == 0
        stream.reader._transport.resume_reading()
        everything = [f for frames in rounds for f in frames]
        got, = await _receive_all([stalled], len(everything))
        assert got == everything
        assert broker.connections.num_users == 3 and not plane.disabled


def _marked(plane) -> tuple:
    """``describe()`` and the clock at one instant: the pump's open state
    is credited up to now first (re-entered), so the six state counters
    sum to the pump's whole life."""
    import time
    account = plane._account
    account.enter(account._state)
    now = time.monotonic_ns()
    return plane.describe(), now


async def test_paced_takes_add_up_to_the_account_inside_the_gate(monkeypatch):
    """Served over TCP off saturation, the sends the period
    (``_costly_sends``) and each step 30 ms of CPU beyond its wall (added
    on the worker thread, as the batch's threads add it): the first step
    goes one by one, the three after it in the batch, and the take after
    each of those waits until the wall since that step's take has caught
    up with its CPU. The waits, timed around each ``_pace``, sum to what
    ``pump_paced_us`` moved by, inside what ``pump_gate_us`` moved by,
    and the six state counters still move by what the clock moved by."""
    import time

    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig

    rounds = [[b"round %d %d" % (r, i) for i in range(_LANE - 1)]
              for r in range(4)]
    async with _served_over_tcp(
            3150, DevicePlaneConfig(**_BATCH_PLANE), [{0}] * 3) as (
                broker, clients):
        plane = broker.device_plane
        cost, ahead = _costly_sends(monkeypatch, plane)
        calls = _record_batches(monkeypatch, before=cost)

        def in_worker(_n_taken):
            ahead[1] += 30 * _MS
        _record_takes(plane, in_worker)
        paces = []      # (owed at the call, ns it returned, ns it took)
        real = plane._pace

        async def pace():
            owed, t0 = plane._pacer.owed_ns(), time.monotonic_ns()
            waited = await real()
            paces.append((owed, waited, time.monotonic_ns() - t0))
            return waited
        plane._pace = pace
        before, t0_ns = _marked(plane)
        for frames in rounds:
            os.write(_socket_of(clients[0]), _wire(*frames))
            assert await _receive_all(clients, len(frames)) == [frames] * 3
        await wait_until(lambda: len(paces) == 3 and not plane._between_steps)
        after, t1_ns = _marked(plane)
    moved = {k: after[k] - before[k] for k in after
             if k.startswith("pump_") or k in ("steps", "egress_batched",
                                                "egress_offsat_batched")}
    assert (moved["steps"], len(calls)) == (4, 3)
    assert moved["egress_batched"] == moved["egress_offsat_batched"] == 9
    assert all(owed > 0 and owed - _MS <= waited <= took
               for owed, waited, took in paces), paces
    assert moved["pump_paced_steps"] == 3
    assert moved["pump_paced_us"] == sum(w // 1000 for _, w, _ in paces)
    assert sum(w for _, w, _ in paces) <= sum(t for *_, t in paces) \
        <= sum(w for _, w, _ in paces) + 3 * 2 * _MS
    assert moved["pump_paced_us"] <= moved["pump_gate_us"]
    states = sum(moved[f"pump_{s}_us"] for s in (
        "parked", "gate", "drain", "take", "worker", "egress"))
    assert states == pytest.approx((t1_ns - t0_ns) / 1e3, abs=50)


@pytest.mark.parametrize("err", ["EPIPE", "ECONNRESET"])
async def test_a_send_that_fails_in_the_batch_removes_that_user_only(
        err, monkeypatch):
    import errno
    import socket

    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from pushcdn_tpu.broker.tasks import senders

    code = getattr(errno, err)
    victim_fd = []

    def shut_down(fds):     # the peer is gone by the time of the send()
        if victim_fd:
            assert victim_fd[0] in fds
            sock = socket.socket(fileno=os.dup(victim_fd[0]))
            sock.shutdown(socket.SHUT_RDWR)
            sock.close()

    def as_err(fds, sent):
        if victim_fd:
            at = fds.index(victim_fd.pop())
            assert sent[at] == -errno.EPIPE
            sent[at] = -code
        return sent
    calls = _record_batches(monkeypatch, shut_down, as_err)
    failed = []
    real = senders._send_failed

    def send_failed(broker, key, connection, exc):
        failed.append((key, exc))
        real(broker, key, connection, exc)
    monkeypatch.setattr(senders, "_send_failed", send_failed)

    first = [b"first %d" % i for i in range(_LANE)]
    second = [b"second %d" % i for i in range(_LANE)]
    async with _served_over_tcp(
            3120, DevicePlaneConfig(**_BATCH_PLANE), [{0}] * 4) as (
                broker, clients):
        plane = broker.device_plane
        victim = clients[3]
        victim_fd.append(_broker_fd(broker, victim))
        os.write(_socket_of(clients[0]), _wire(*first))
        got = await _receive_all(clients[:3], _LANE)
        assert got == [first] * 3
        (key, exc), = failed
        assert key == victim.public_key and os.strerror(code) in str(exc)
        assert broker.connections.get_user_connection(key) is None
        assert broker.connections.num_users == 3
        assert (plane.egress_batched, plane.egress_inline,
                plane.messages_routed) == (3, 3, 3 * _LANE)
        # the next step: the three that are left, batched again
        os.write(_socket_of(clients[0]), _wire(*second))
        got = await _receive_all(clients[:3], _LANE)
        assert got == [second] * 3
        assert len(calls) == 2 and len(calls[1][0]) == 3
        assert plane.egress_batched == 6 and not plane.disabled


@pytest.mark.parametrize("link", ["tls", "memory"])
async def test_links_without_a_plain_idle_socket_are_never_batched(
        link, monkeypatch):
    """A TLS transport has a socket, but not one that carries the stream's
    bytes; the Memory transport has none. Their steps are back-pressured
    like any other and go one by one (a link whose transport holds bytes:
    the short send's test above)."""
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from pushcdn_tpu.proto.transport import Memory, TcpTls

    payloads = [b"frame %d" % i for i in range(_LANE)]
    calls = _record_batches(monkeypatch)
    async with _served_over_tcp(
            3130, DevicePlaneConfig(**_BATCH_PLANE), [{0}] * 3,
            protocol=TcpTls if link == "tls" else Memory) as (
                broker, clients):
        plane = broker.device_plane
        conns = [broker.connections.get_user_connection(c.public_key)
                 for c in clients]
        if link == "tls":
            assert all(c._stream.writer.get_extra_info("socket") is not None
                       for c in conns)
        assert [c.idle_fd(1) for c in conns] == [None] * 3
        # no socket to write into: one pipelined burst fills the lane
        await asyncio.gather(*(
            clients[0].send_broadcast_message([0], p) for p in payloads))
        got = await _receive_all(clients, _LANE)
        assert got == [payloads] * 3
        assert plane.egress_batched == 0 and not calls
        assert plane.egress_inline + plane.egress_queued == 3 * plane.steps


# ---------------------------------------------------------------------------
# the batch's settling (ISSUE 38): the sends that took their whole stream are
# settled in one pass (the plane's tallies by their sums, the process-wide
# counters and the ledger once a (transport label, ledger peer) with the
# totals); every other entry goes through ``Connection.sent_on_fd`` as before
# and is counted in ``egress_batched_short``.
# ---------------------------------------------------------------------------

class _NeverWhole(np.ndarray):
    """What ``send_batch`` returned, equal to nothing: the comparison
    that picks the whole sends finds none, so every entry of the batch
    is settled by ``Connection.sent_on_fd``, as before ISSUE 38."""

    def __eq__(self, other):
        return np.zeros(self.shape, bool)

    __hash__ = None


def _never_whole(fds, sent):
    """``_record_batches``'s ``after`` that forces the per-link path."""
    return sent.view(_NeverWhole)


def _watch_settling(monkeypatch) -> tuple:
    """``(each, together)``: every ``Connection.sent_on_fd`` call as
    ``(link, sent)`` and every ``sent_whole_on_fds`` call as ``(links,
    nbytes, nframes)`` lists, both still made."""
    from pushcdn_tpu.proto.transport.base import Connection
    each, together = [], []
    real_each, real_together = Connection.sent_on_fd, \
        Connection.sent_whole_on_fds

    def sent_on_fd(self, data, sent, **kwargs):
        each.append((self, sent))
        return real_each(self, data, sent, **kwargs)

    def sent_whole_on_fds(links, nbytes, nframes, **kwargs):
        together.append((list(links), nbytes.tolist(), nframes.tolist()))
        return real_together(links, nbytes, nframes, **kwargs)
    monkeypatch.setattr(Connection, "sent_on_fd", sent_on_fd)
    monkeypatch.setattr(Connection, "sent_whole_on_fds",
                        staticmethod(sent_whole_on_fds))
    return each, together


def _stall_reader(link, client) -> None:
    """``client`` stops reading behind socket buffers of 4 KB at both
    ends of ``link``: a stream of tens of KB gets a short ``send()``.
    ``client._connection._stream.reader._transport.resume_reading()``
    undoes it."""
    import socket
    stream = client._connection._stream
    stream.writer.get_extra_info("socket").setsockopt(
        socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
    link._stream.writer.get_extra_info("socket").setsockopt(
        socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
    stream.reader._transport.pause_reading()


def _shut_down_before_the_batch(gone: list):
    """``_record_batches``'s ``before``: the peer of the fd in ``gone``
    (popped) is gone by the time of the ``send()``, which gets
    ``EPIPE``."""
    import socket

    def shut_down(fds):
        if gone:
            sock = socket.socket(fileno=os.dup(gone.pop()))
            sock.shutdown(socket.SHUT_RDWR)
            sock.close()
    return shut_down


def _egress_account(plane) -> dict:
    """Every sum a settled send moves, flat: the process-wide counters,
    the ledger, the plane's tallies."""
    from pushcdn_tpu.proto import ledger as ledger_mod
    from pushcdn_tpu.proto import metrics as metrics_mod
    book = ledger_mod.LEDGER
    out = {("bytes_sent",) + k: c.value
           for k, c in metrics_mod.BYTES_SENT._children.items()}
    for name, family in (("class_frames_out", metrics_mod.CLASS_FRAMES_OUT),
                         ("class_bytes_out", metrics_mod.CLASS_BYTES_OUT)):
        out.update({(name, i): c.value for i, c in enumerate(family)})
    out.update({("queued", i): n for i, n in enumerate(book.queued)})
    out.update({("fate",) + k + (i,): n for k, row in book.fates.items()
                for i, n in enumerate(row)})
    out.update({("plane", k): getattr(plane, k) for k in (
        "messages_routed", "egress_inline", "egress_queued",
        "egress_batched", "egress_batched_short")})
    return out


def _moved(before: dict, after: dict) -> dict:
    """What ``_egress_account`` moved by, the keys that did not left out."""
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def _seeded_rounds(seed: int, lane: int = _LANE) -> list:
    """Rounds of ``lane`` frames (a full base lane each: a back-pressured
    step), sizes drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    return [[(b"%d.%d|" % (r, i)).ljust(int(rng.integers(8, 900)), b".")
             for i in range(lane)] for r in range(int(rng.integers(2, 5)))]


async def _settled_rounds(seed: int, monkeypatch, per_link: bool):
    """``_seeded_rounds`` through one broker with four subscribers that
    keep up; what ``_egress_account`` moved by over them, and how many
    ``sent_on_fd`` calls settled them."""
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from pushcdn_tpu.proto import ledger as ledger_mod

    one_by_one, _ = _watch_settling(monkeypatch)
    calls = _record_batches(monkeypatch, after=_never_whole) if per_link \
        else _record_batches(monkeypatch)
    rounds = _seeded_rounds(seed)
    async with _served_over_tcp(
            seed, DevicePlaneConfig(**_BATCH_PLANE), [{0}] * 4) as (
                broker, clients):
        plane = broker.device_plane
        before = _egress_account(plane)
        for r, frames in enumerate(rounds):
            os.write(_socket_of(clients[0]), _wire(*frames))
            assert await _receive_all(clients, _LANE) == [frames] * 4
            assert plane.steps == r + 1
        moved = _moved(before, _egress_account(plane))
        book = ledger_mod.LEDGER
        assert book.walk_live_queues() == 0
        assert book.derived_in_queue() == [0] * len(book.queued)
    assert [sent for _, nbytes, sent in calls] == \
        [nbytes for _, nbytes, sent in calls]       # every send was whole
    assert len(calls) == len(rounds)
    return moved, len(one_by_one), rounds


@pytest.mark.parametrize("seed", [3801, 3802, 3803])
async def test_settling_in_one_pass_leaves_what_settling_each_link_leaves(
        seed, monkeypatch):
    from pushcdn_tpu.proto import flowclass

    bulk, bulk_calls, rounds = await _settled_rounds(seed, monkeypatch,
                                                     per_link=False)
    with monkeypatch.context() as patched:
        each, each_calls, _ = await _settled_rounds(seed, patched,
                                                    per_link=True)
    handoffs = 4 * len(rounds)
    assert (bulk_calls, each_calls) == (0, handoffs)
    assert bulk.pop(("plane", "egress_batched_short"), 0) == 0
    assert each.pop(("plane", "egress_batched_short")) == handoffs
    assert bulk == each
    # and both are what the steps delivered: every frame to all four,
    # each stream a frame's u32 length and its bytes
    frames = 4 * _LANE * len(rounds)
    wire = 4 * len(_wire(*(f for frames_ in rounds for f in frames_)))
    live = flowclass.LIVE
    assert bulk == {
        ("bytes_sent", "tcp"): wire, ("class_bytes_out", live): wire,
        ("class_frames_out", live): frames, ("queued", live): frames,
        ("fate", "delivered", "egress", live): frames,
        ("plane", "messages_routed"): frames,
        ("plane", "egress_inline"): handoffs,
        ("plane", "egress_batched"): handoffs}


async def test_a_mixed_batch_settles_the_whole_sends_together_and_the_rest_each(
        monkeypatch):
    """One batch with two readers that keep up, one that has stopped
    reading (a short send) and one whose peer is gone (``EPIPE``): the
    two whole sends are settled in one ``sent_whole_on_fds`` call, the
    two others by ``sent_on_fd`` in batch order; ``egress_batched_short``
    counts the short one, the failed one is in no tally and its user is
    removed, and nobody's frames change order."""
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from pushcdn_tpu.broker.tasks import senders

    lane = 48   # 48 KB a user a step: over what a stalled link's buffers take
    first = [(b"0.%d|" % i).ljust(1000, b".") for i in range(lane)]
    second = [(b"1.%d|" % i).ljust(1000, b".") for i in range(lane)]
    gone, failed = [], []
    calls = _record_batches(monkeypatch, _shut_down_before_the_batch(gone))
    each, together = _watch_settling(monkeypatch)
    real_failed = senders._send_failed

    def send_failed(broker, key, connection, exc):
        failed.append(key)
        real_failed(broker, key, connection, exc)
    monkeypatch.setattr(senders, "_send_failed", send_failed)
    async with _served_over_tcp(
            3810, DevicePlaneConfig(**dict(_BATCH_PLANE, ring_slots=lane)),
            [{0}] * 4) as (broker, clients):
        plane = broker.device_plane
        stalled, victim = clients[2], clients[3]
        links = [broker.connections.get_user_connection(c.public_key)
                 for c in clients]
        _stall_reader(links[2], stalled)
        stalled_fd, victim_fd = (_broker_fd(broker, c)
                                 for c in (stalled, victim))
        gone.append(victim_fd)
        before = _egress_account(plane)
        os.write(_socket_of(clients[0]), _wire(*first))
        assert await _receive_all(clients[:2], lane) == [first] * 2
        (fds, nbytes, sent), = calls
        assert len(fds) == 4 and len(set(nbytes)) == 1
        size = nbytes[0]
        short_at, failed_at = fds.index(stalled_fd), fds.index(victim_fd)
        assert 0 <= sent[short_at] < size or sent[short_at] == -errno.EAGAIN
        assert sent[failed_at] == -errno.EPIPE
        # those two one by one, in batch order; the two others together
        assert each == [(link, sent[at]) for at, link in sorted(
            [(short_at, links[2]), (failed_at, links[3])])]
        (whole, whole_bytes, whole_frames), = together
        assert sorted(map(id, whole)) == sorted(map(id, links[:2]))
        assert (whole_bytes, whole_frames) == ([size] * 2, [lane] * 2)
        assert failed == [victim.public_key]
        assert broker.connections.get_user_connection(
            victim.public_key) is None
        assert broker.connections.num_users == 3
        moved = _moved(before, _egress_account(plane))
        assert {k[1]: v for k, v in moved.items() if k[0] == "plane"} == {
            "messages_routed": 3 * lane, "egress_inline": 3,
            "egress_batched": 3, "egress_batched_short": 1}
        assert moved[("bytes_sent", "tcp")] == 3 * size
        assert plane.describe()["egress_batched_short"] == 1
        # the next step: the two that read are settled together again;
        # the stalled link is batched only if its transport could write
        # the remainder at once, and then comes back short again
        os.write(_socket_of(clients[0]), _wire(*second))
        assert await _receive_all(clients[:2], lane) == [second] * 2
        fds, nbytes, sent = calls[1]
        assert victim_fd not in fds and len(fds) in (2, 3)
        shorts = 1 + sum(n != s for n, s in zip(nbytes, sent))
        assert plane.egress_batched_short == shorts == len(each) - 1
        assert [len(t[0]) for t in together] == [2, 2]
        stalled._connection._stream.reader._transport.resume_reading()
        got, = await _receive_all([stalled], 2 * lane)
        assert got == first + second
        assert broker.connections.num_users == 3 and not plane.disabled


@pytest.mark.parametrize("apart_by", ["ledger_peer", "transport_label"])
async def test_links_that_are_accounted_apart_are_credited_apart(
        apart_by, monkeypatch):
    """The one pass credits once for each distinct (transport label,
    ledger peer) among the whole sends: a link whose bytes count under
    another transport's label, or whose frames the ledger books as
    relayed to a peer, gets its own totals and the others theirs."""
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from pushcdn_tpu.proto import flowclass
    from pushcdn_tpu.proto import metrics as metrics_mod

    payloads = [(b"frame %d|" % i).ljust(100 + i, b".") for i in range(_LANE)]
    calls = _record_batches(monkeypatch)
    async with _served_over_tcp(
            3820, DevicePlaneConfig(**_BATCH_PLANE), [{0}] * 4) as (
                broker, clients):
        plane = broker.device_plane
        odd = broker.connections.get_user_connection(clients[1].public_key)
        if apart_by == "ledger_peer":
            odd.ledger_peer = "a-peer"
        else:
            odd._m_sent = metrics_mod.BYTES_SENT.labels(transport="other")
        before = _egress_account(plane)
        os.write(_socket_of(clients[0]), _wire(*payloads))
        assert await _receive_all(clients, _LANE) == [payloads] * 4
        moved = _moved(before, _egress_account(plane))
    (fds, nbytes, sent), = calls
    assert len(fds) == 4 and sent == nbytes
    size, live = len(_wire(*payloads)), flowclass.LIVE
    want = {("class_bytes_out", live): 4 * size,
            ("class_frames_out", live): 4 * _LANE,
            ("queued", live): 4 * _LANE,
            ("plane", "messages_routed"): 4 * _LANE,
            ("plane", "egress_inline"): 4, ("plane", "egress_batched"): 4}
    if apart_by == "ledger_peer":
        want.update({("bytes_sent", "tcp"): 4 * size,
                     ("fate", "delivered", "egress", live): 3 * _LANE,
                     ("fate", "relayed", "mesh", live): _LANE})
    else:
        want.update({("bytes_sent", "tcp"): 3 * size,
                     ("bytes_sent", "other"): size,
                     ("fate", "delivered", "egress", live): 4 * _LANE})
    assert moved == want
