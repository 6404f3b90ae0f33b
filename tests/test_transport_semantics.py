"""Direct contract tests for the round-2/3 transport semantics.

Each test pins a documented contract that would otherwise only fail
indirectly (a stalled broker, a leaked pool permit) rather than as an
assert: ``send_raw_many``'s always-released ownership rule, the limiter's
``try_allocate`` FIFO fairness, the native ``FrameEncoder``'s
capacity-overflow fallback, ``deserialize_owned``'s malformed-frame error
parity with ``deserialize``, and the reader/writer cancel-safety paths
added in round 3.
"""

import asyncio
import struct

import numpy as np
import pytest

from pushcdn_tpu import native
from pushcdn_tpu.proto import MAX_MESSAGE_SIZE
from pushcdn_tpu.proto.error import Error, ErrorKind
from pushcdn_tpu.proto.limiter import Bytes, Limiter, MemoryPool
from pushcdn_tpu.proto.message import (
    KIND_BROADCAST,
    KIND_DIRECT,
    Broadcast,
    Direct,
    deserialize,
    deserialize_owned,
    serialize,
)
from pushcdn_tpu.proto.transport import Memory, Tcp

_LEN = struct.Struct(">I")


async def _pair(endpoint: str, limiter: Limiter = None, client_limiter=None):
    from pushcdn_tpu.proto.limiter import NO_LIMIT
    listener = await Memory.bind(endpoint)
    connect = asyncio.create_task(
        Memory.connect(endpoint, limiter=client_limiter or NO_LIMIT))
    server = await (await listener.accept()).finalize(
        limiter=limiter or NO_LIMIT)
    client = await connect
    return listener, client, server


# ---------------------------------------------------------------------------
# send_raw_many ownership: frames are ALWAYS released by the connection
# ---------------------------------------------------------------------------

async def test_send_raw_many_on_poisoned_connection_releases_exactly_once():
    pool = MemoryPool(64 * 1024)
    listener, client, server = await _pair("sem-poisoned")
    # poison the client connection by killing the peer and forcing a write
    server.close()
    await client.send_raw(serialize(Direct(recipient=b"r", message=b"x")))
    for _ in range(200):
        if client.is_closed:
            break
        await asyncio.sleep(0.01)
    frames = [Bytes(b"p" * 128, None) for _ in range(4)]
    permits = [await pool.allocate(128) for _ in range(4)]
    for f, p in zip(frames, permits):
        f._permit = p
    with pytest.raises(Error):
        await client.send_raw_many(frames)
    # released exactly once: pool back to capacity, refcounts at zero
    assert pool.available == 64 * 1024
    assert all(f._refs[0] == 0 for f in frames)
    client.close()
    await listener.close()


async def test_send_raw_many_cancelled_while_blocked_releases():
    # bounded per-connection queue: the put blocks, cancellation must
    # release every frame in the never-inserted batch. The accepted side is
    # never finalized, so nothing drains the 8 KiB duplex window and the
    # client writer genuinely stalls mid-flush.
    pool = MemoryPool(64 * 1024)
    lim = Limiter(per_connection_queue=1)
    listener = await Memory.bind("sem-cancelled")
    connect = asyncio.create_task(Memory.connect("sem-cancelled",
                                                 limiter=lim))
    _unfinalized = await listener.accept()
    client = await connect
    # top the queue up across ticks: the writer takes one frame and blocks
    # mid-flush on the full window, then the bounded queue stays full
    for _ in range(5):
        try:
            while True:
                client.send_raw_nowait(Bytes(b"z" * 8192, None))
        except asyncio.QueueFull:
            pass
        await asyncio.sleep(0.01)
    frames = [Bytes(b"q" * 64, await pool.allocate(64)) for _ in range(5)]
    task = asyncio.create_task(client.send_raw_many(frames))
    await asyncio.sleep(0.05)
    assert not task.done()  # genuinely blocked on the bounded queue
    task.cancel()
    with pytest.raises(asyncio.CancelledError):
        await task
    assert pool.available == 64 * 1024
    assert all(f._refs[0] == 0 for f in frames)
    client.close()
    await listener.close()


# ---------------------------------------------------------------------------
# try_allocate FIFO fairness
# ---------------------------------------------------------------------------

async def test_try_allocate_never_jumps_a_waiter():
    pool = MemoryPool(100)
    held = await pool.allocate(80)
    waiter = asyncio.create_task(pool.allocate(60))
    await asyncio.sleep(0.01)
    assert not waiter.done()
    # 10 bytes ARE available, but granting them would jump the FIFO waiter
    assert pool.try_allocate(10) is None
    held.release()
    permit = await waiter
    assert pool.available == 40
    # with no waiters, try_allocate takes the sync fast path
    fast = pool.try_allocate(40)
    assert fast is not None
    permit.release()
    fast.release()
    assert pool.available == 100


# ---------------------------------------------------------------------------
# FrameEncoder capacity-overflow fallback
# ---------------------------------------------------------------------------

def test_frame_encoder_overflow_returns_none():
    enc = native.FrameEncoder.create(capacity=256)
    if enc is None:
        pytest.skip("native library unavailable")
    ok = enc.encode([b"a" * 32, b"b" * 32])
    assert ok is not None and len(ok) == 72
    ok.release()
    # total (4+200)*2 > 256: must refuse, not truncate
    assert enc.encode([b"c" * 200, b"d" * 200]) is None


def test_frame_encoder_buffer_grows_with_the_batches_up_to_capacity():
    """An encoder costs its owner (every connection's writer) 4 KiB until
    a batch asks for more; up to ``capacity`` the stream is the same
    bytes, beyond it the encoder still refuses."""
    import struct
    enc = native.FrameEncoder.create(capacity=256 * 1024)
    if enc is None:
        pytest.skip("native library unavailable")
    assert len(enc._out) == 4096

    def framed(payloads):
        return b"".join(struct.pack(">I", len(p)) + p for p in payloads)

    for payloads in ([b"a" * 1000], [b"b" * 3000, b"c" * 3000],
                     [bytes([i]) * 9000 for i in range(20)], [b"d" * 10]):
        view = enc.encode(payloads)
        assert bytes(view) == framed(payloads)
        view.release()
    assert 180_080 <= len(enc._out) <= 256 * 1024
    assert enc.encode([b"e" * 9000] * 30) is None      # 270 KB: refused
    view = enc.encode([b"f" * 262_140])                # exactly capacity
    assert len(view) == 256 * 1024 == len(enc._out)
    view.release()


async def test_writer_falls_back_when_batch_exceeds_encoder_capacity():
    # a queued batch far beyond the native encoder capacity must still
    # arrive intact via the Python coalescing fallback
    listener, client, server = await _pair("sem-encoder-overflow")
    payloads = [serialize(Broadcast(topics=[0], message=bytes([i]) * 3000))
                for i in range(128)]
    await client.send_raw_many([Bytes(p, None) for p in payloads])
    got = []
    while len(got) < 128:
        raws = await asyncio.wait_for(server.recv_raw_many(), 5)
        got.extend(bytes(r.data) for r in raws)
        for r in raws:
            r.release()
    assert got == payloads
    client.close()
    server.close()
    await listener.close()


# ---------------------------------------------------------------------------
# deserialize_owned malformed-frame parity
# ---------------------------------------------------------------------------

def test_deserialize_owned_truncated_raises_error_not_struct_error():
    # 1-4 byte truncated Direct/Broadcast frames: the fast path must raise
    # the same Error(DESERIALIZE) the two-step path does — the broker's
    # malformed-frame disconnect policy catches Error only
    for frame in (bytes([KIND_DIRECT]), bytes([KIND_DIRECT, 0, 0]),
                  bytes([KIND_BROADCAST]), bytes([KIND_BROADCAST, 1])):
        with pytest.raises(Error) as ei:
            deserialize_owned(frame)
        assert ei.value.kind == ErrorKind.DESERIALIZE
        with pytest.raises(Error):
            deserialize(frame)


def test_deserialize_owned_oversize_parity():
    frame = bytes([KIND_DIRECT]) + b"\x00" * (MAX_MESSAGE_SIZE + 4)
    with pytest.raises(Error) as ei:
        deserialize_owned(frame)
    assert ei.value.kind == ErrorKind.EXCEEDED_SIZE


def test_deserialize_owned_matches_deserialize_on_valid_frames():
    for msg in (Direct(recipient=b"rcpt", message=b"payload"),
                Broadcast(topics=[1, 7], message=b"payload2")):
        frame = serialize(msg)
        owned = deserialize_owned(frame)
        two_step = deserialize(frame)
        assert type(owned) is type(two_step)
        assert bytes(owned.message) == bytes(two_step.message)


# ---------------------------------------------------------------------------
# recv error interleaving + cancel safety (round-3 paths)
# ---------------------------------------------------------------------------

async def test_recv_raw_many_delivers_frames_before_surfacing_error():
    listener, client, server = await _pair("sem-err-interleave")
    for i in range(3):
        await client.send_message(Direct(recipient=b"r", message=bytes([i])))
    # wait until the frames are parsed server-side, then kill the link
    await asyncio.sleep(0.05)
    client.close()
    got = 0
    with pytest.raises(Error):
        while True:
            raws = await asyncio.wait_for(server.recv_raw_many(), 5)
            got += len(raws)
            for r in raws:
                r.release()
    assert got == 3  # queued frames delivered before the poison surfaced
    server.close()
    await listener.close()


async def test_flush_sender_not_stranded_by_close():
    # a flush=True sender whose entry was dequeued must not await forever
    # when close() cancels the writer mid-flush; the accepted side is never
    # finalized, so the 64 KiB frame blocks in the 8 KiB duplex window
    listener = await Memory.bind("sem-flush-cancel")
    connect = asyncio.create_task(Memory.connect("sem-flush-cancel"))
    _unfinalized = await listener.accept()
    client = await connect
    blocker = asyncio.create_task(
        client.send_raw(b"w" * (64 * 1024), flush=True))
    await asyncio.sleep(0.05)
    assert not blocker.done()  # writer is mid-flush
    client.close()
    with pytest.raises((asyncio.CancelledError, Error)):
        await asyncio.wait_for(blocker, 5)
    await listener.close()


async def test_close_with_queued_bare_frame_returns_pool_bytes():
    # the reader's depth-1 fast path queues bare Bytes; close() must drain
    # them back into the pool like list batches
    pool_lim = Limiter(global_pool_bytes=32 * 1024)
    listener, client, server = await _pair("sem-bare-drain",
                                           limiter=pool_lim)
    await client.send_message(Direct(recipient=b"r", message=b"m" * 512))
    await asyncio.sleep(0.05)  # parsed and queued, never received
    server.close()
    await asyncio.sleep(0.05)
    assert pool_lim.pool.available == 32 * 1024
    client.close()
    await listener.close()


async def test_send_encoded_nowait_bounded_queue_fails_fast():
    """The device-plane egress handoff must FAIL (QueueFull), never block,
    when a slow consumer's bounded send queue is full — that failure is
    what triggers the sender-side removal policy, so one stalled client
    cannot stall the pump."""
    import asyncio

    from pushcdn_tpu.proto.limiter import Limiter
    from pushcdn_tpu.proto.transport.memory import (
        gen_testing_connection_pair,
    )

    a, b = await gen_testing_connection_pair(
        Limiter(None, per_connection_queue=2))
    try:
        # the peer never reads and the writer stalls on the tiny duplex
        # window, so entries pile up in the bounded send queue
        big = b"\x00" * 64 * 1024
        for _ in range(8):
            try:
                a.send_encoded_nowait(
                    len(big).to_bytes(4, "big") + big)
            except asyncio.QueueFull:
                break
            await asyncio.sleep(0)
        else:
            raise AssertionError("bounded queue never filled")
    finally:
        a.close()
        b.close()


async def test_bounded_queue_send_order_is_fifo_under_saturation():
    """Bounded connections take the awaited ``q.put`` path (no
    put_nowait fast path): a saturated sequential sender's frames
    transmit in send order, and a putter blocked on a full queue makes
    progress as the writer drains (liveness). asyncio.Queue gives no
    hard slot reservation against a RACING second sender, so this pins
    ordering/liveness for the saturated path, not a global FIFO across
    concurrent senders."""
    lim = Limiter(per_connection_queue=2)
    listener = await Memory.bind("sem-fifo-order")
    connect = asyncio.create_task(Memory.connect("sem-fifo-order",
                                                 limiter=lim))
    server = await (await listener.accept()).finalize()
    client = await connect

    n = 40
    sent = [b"frame-%03d" % i for i in range(n)]

    async def sender():
        for payload in sent:
            await client.send_raw(payload)

    task = asyncio.create_task(sender())
    got = []
    async with asyncio.timeout(10):
        while len(got) < n:
            raw = await server.recv_raw()
            got.append(bytes(raw.data))
            raw.release()
            # stall the drain a tick so the bounded queue saturates and
            # blocked puts interleave with freed slots
            await asyncio.sleep(0)
    await task
    assert got == sent  # exact send order, no slot-stealing reorder
    client.close()
    server.close()
    await listener.close()


# ---------------------------------------------------------------------------
# the inline step flush: Connection.try_send_encoded_inline /
# RawStream.write_nowait (an idle link is written by the caller's task;
# anything else takes the writer, in order)
# ---------------------------------------------------------------------------

async def _tcp_pair():
    listener = await Tcp.bind("127.0.0.1:0")
    connect = asyncio.create_task(
        Tcp.connect(f"127.0.0.1:{listener.bound_port}"))
    server = await (await listener.accept()).finalize()
    client = await connect
    return listener, client, server


def _stream_of(*payloads: bytes) -> bytes:
    return b"".join(_LEN.pack(len(p)) + p for p in payloads)


async def _recv_n(conn, n: int) -> list:
    got = []
    async with asyncio.timeout(10):
        while len(got) < n:
            for raw in await conn.recv_raw_many():
                got.append(bytes(raw.data))
                raw.release()
    return got


async def test_inline_then_queued_then_inline_keeps_the_wire_fifo():
    listener, client, server = await _tcp_pair()
    try:
        # idle link: written there and then, no writer task at all
        assert server.try_send_encoded_inline(
            memoryview(_stream_of(b"a0", b"a1")), nframes=2)
        assert server._writer_task is None
        # a queued entry makes the link non-idle: the next stream may not
        # overtake it, so the caller's fallback queues behind it
        server.send_encoded_nowait(_stream_of(b"b0"), nframes=1)
        assert not server.try_send_encoded_inline(_stream_of(b"c0"),
                                                  nframes=1)
        server.send_encoded_nowait(_stream_of(b"c0"), nframes=1)
        assert await _recv_n(client, 4) == [b"a0", b"a1", b"b0", b"c0"]
        # drained: idle again, inline again
        assert server._send_q.empty() and not server._write_mutex.locked()
        assert server.try_send_encoded_inline(_stream_of(b"d0", b"d1"),
                                              nframes=2)
        assert await _recv_n(client, 2) == [b"d0", b"d1"]
    finally:
        client.close()
        server.close()
        await listener.close()


async def _hold_mutex(server):
    await server._write_mutex.acquire()


async def _queue_entry(server):
    server.send_encoded_nowait(_stream_of(b"queued"), nframes=1)


async def _over_the_mark(server):
    # a peer that does not read: streams of just under one flush unit
    # until the socket is full and one is left waiting in the
    # transport's buffer, far over the marks
    server._stream.writer.transport.set_write_buffer_limits(high=8192)
    big = _stream_of(b"x" * 65000)
    for _ in range(1024):
        if not server.try_send_encoded_inline(big, nframes=1):
            break
    assert server._stream.writer.transport.get_write_buffer_size() > 8192


async def _a_long_stream(server):
    # an idle link, but more than one flush unit
    return _stream_of(*[b"l" * 40000] * 2)


async def _poison(server):
    server._poison(Error(ErrorKind.CONNECTION, "test"))


async def _close(server):
    server.close()


@pytest.mark.parametrize("prepare, dead", [
    (_queue_entry, False), (_hold_mutex, False), (_over_the_mark, False),
    (_poison, True), (_close, True), (_a_long_stream, False),
], ids=["queue_holds_an_entry", "write_mutex_held", "over_the_water_mark",
        "poisoned", "closed", "longer_than_one_flush_unit"])
async def test_inline_is_refused_on_a_link_that_is_not_idle(prepare, dead):
    from pushcdn_tpu.proto import metrics as metrics_mod
    listener, client, server = await _tcp_pair()
    if prepare is _over_the_mark:
        client._reader_task.cancel()  # the peer stops reading
    try:
        stream = await prepare(server) or _stream_of(b"late")
        sent = metrics_mod.BYTES_SENT.labels(transport="tcp").value
        assert server.try_send_encoded_inline(stream, nframes=1) is False
        assert metrics_mod.BYTES_SENT.labels(transport="tcp").value == sent
        if dead:  # and the fallback is what reports the dead link
            with pytest.raises(Error):
                server.send_encoded_nowait(_stream_of(b"late"), nframes=1)
    finally:
        client.close()
        server.close()
        await listener.close()


async def test_inline_is_refused_by_a_stream_without_the_capability():
    listener, client, server = await _pair("sem-inline-memory")
    try:
        assert server._stream.write_nowait(b"") is False
        assert not server.try_send_encoded_inline(_stream_of(b"m0"),
                                                  nframes=1)
        server.send_encoded_nowait(_stream_of(b"m0"), nframes=1)
        assert await _recv_n(client, 1) == [b"m0"]
    finally:
        client.close()
        server.close()
        await listener.close()


class _Broker:
    """What the send helpers use of a broker: its connections (one object
    plays both) and ``update_metrics``."""

    def __init__(self, users: dict):
        self.users = dict(users)
        self.removed = []
        self.connections = self

    def get_user_connection(self, key):
        return self.users.get(key)

    def remove_user(self, key, reason=""):
        self.removed.append((key, reason))
        self.users.pop(key).close()

    def update_metrics(self):
        pass


class _Plane:
    """What ``senders.egress_streams`` uses of a plane: the slot table
    (one user per slot) and the five tallies."""

    def __init__(self, keys):
        self.keys = list(keys)
        self.slots = self
        self.messages_routed = self.egress_inline = self.egress_queued = 0
        self.egress_oversize = self.egress_oversize_bytes = 0

    def key_of(self, slot):
        return self.keys[slot]


class _Streams:
    """A step's native egress: the same ``nframes``-frame stream for each
    of ``n`` users (``native.EgressStreams``' face)."""

    def __init__(self, n: int, stream: bytes, nframes: int):
        self.users = range(n)
        self.msgs = [nframes] * n
        self.nbytes = np.full(n, len(stream), np.int64)
        self._stream = stream

    def stream(self, slot):
        return memoryview(self._stream)


async def test_a_raising_inline_write_poisons_and_egress_removes_the_user():
    """A step's egress over a faulty link and a healthy one: the one is
    poisoned, removed and counts for nothing, the other is served."""
    from pushcdn_tpu.broker.tasks.senders import egress_streams
    listener, client, faulty = await _tcp_pair()
    listener2, client2, fine = await _tcp_pair()
    try:
        def boom(data):
            raise OSError("wire fault")
        faulty._stream.write_nowait = boom
        broker = _Broker({b"faulty": faulty, b"fine": fine})
        plane = _Plane([b"faulty", b"fine"])
        egress_streams(plane, broker, _Streams(2, _stream_of(b"y0", b"y1"), 2))
        assert faulty._error is not None  # as after a failed writer flush
        assert broker.removed == [(b"faulty", "send failed")]
        assert (plane.messages_routed, plane.egress_inline,
                plane.egress_queued) == (2, 1, 0)
        assert await _recv_n(client2, 2) == [b"y0", b"y1"]
    finally:
        for c in (client, faulty, client2, fine):
            c.close()
        await listener.close()
        await listener2.close()


async def test_inline_and_queued_hand_offs_close_the_ledger_identity():
    from pushcdn_tpu.broker.tasks.senders import egress_streams
    from pushcdn_tpu.proto import ledger as ledger_mod
    ledger_mod.reset_for_tests()
    listener, client, server = await _tcp_pair()
    try:
        broker, plane = _Broker({b"u": server}), _Plane([b"u"])
        streams = _Streams(1, _stream_of(b"f0", b"f1", b"f2"), 3)
        egress_streams(plane, broker, streams)
        egress_streams(plane, broker, streams)
        # a host-routed frame queued just before the third step's egress:
        # that hand-off goes behind it (note_queued, then on_dequeued)
        server.send_encoded_nowait(_stream_of(b"g0"), nframes=1)
        egress_streams(plane, broker, streams)
        assert (plane.messages_routed, plane.egress_inline,
                plane.egress_queued) == (9, 2, 1)
        assert await _recv_n(client, 10) == [b"f0", b"f1", b"f2"] * 2 \
            + [b"g0", b"f0", b"f1", b"f2"]
        L = ledger_mod.LEDGER
        assert sum(L.queued) == 10
        assert sum(L.fates[("delivered", "egress")]) == 10
        assert sum(L.derived_in_queue()) == L.walk_live_queues() == 0
        for _ in range(3):
            L.check_conservation()
        assert L.violations == 0
    finally:
        client.close()
        server.close()
        await listener.close()
        ledger_mod.reset_for_tests()


async def test_a_peer_that_stops_reading_is_still_removed_by_the_timeout(
        monkeypatch):
    """Inline writes only ever fill an idle link; once the peer stops
    reading, the next hand-off queues, the writer's ``drain()`` waits, and
    the write timeout poisons the link: the hand-off after that removes
    the user. Slow-consumer detection is the writer's, unchanged."""
    from pushcdn_tpu.broker.tasks.senders import egress_streams
    from pushcdn_tpu.proto.transport import base as base_mod
    monkeypatch.setattr(base_mod, "WRITE_TIMEOUT_S", 0.3)
    listener = await Tcp.bind("127.0.0.1:0")
    # a raw peer that never reads (a Connection's reader always does)
    _reader, peer = await asyncio.open_connection("127.0.0.1",
                                                  listener.bound_port)
    server = await (await listener.accept()).finalize()
    broker, plane = _Broker({b"slow": server}), _Plane([b"slow"])
    streams = _Streams(1, _stream_of(b"s" * 65000), 1)
    try:
        tallies = []
        async with asyncio.timeout(10):
            while not broker.removed:
                egress_streams(plane, broker, streams)
                tallies.append((plane.egress_inline, plane.egress_queued))
                if plane.egress_queued:  # backed up: now give it time
                    await asyncio.sleep(0.05)
        assert tallies[0] == (1, 0)      # the idle link took the first
        assert tallies[-1][1] >= 1       # then the writer, which waited
        assert tallies[-1] == tallies[-2]  # the last hand-off: removal
        assert broker.removed == [(b"slow", "send failed")]
    finally:
        peer.close()
        server.close()
        await listener.close()
