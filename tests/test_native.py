"""C++ framing kernel tests: compiled-on-demand, equivalent to the Python
paths (native/framing.cpp via ctypes)."""

import struct

import numpy as np
import pytest

from pushcdn_tpu import native
from pushcdn_tpu.parallel.frames import FrameRing
from pushcdn_tpu.proto.message import KIND_BROADCAST, KIND_DIRECT

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native lib failed to compile")


def test_pack_frames_matches_python_ring():
    payloads = [b"alpha", b"beta" * 10, b"", b"x" * 64]
    kinds = [KIND_BROADCAST, KIND_DIRECT, KIND_BROADCAST, KIND_DIRECT]
    tmasks = [0b1, 0, 0b10, 0]
    dests = [-1, 5, -1, 7]

    ring_native = FrameRing(slots=8, frame_bytes=64)
    n = ring_native.push_batch(payloads, kinds, tmasks, dests)
    assert n == 4
    native_batch = ring_native.take_batch()

    ring_py = FrameRing(slots=8, frame_bytes=64)
    for p, k, t, d in zip(payloads, kinds, tmasks, dests):
        if k == KIND_BROADCAST:
            ring_py.push_broadcast(p, t)
        else:
            ring_py.push_direct(p, d)
    py_batch = ring_py.take_batch()

    np.testing.assert_array_equal(native_batch.bytes_, py_batch.bytes_)
    np.testing.assert_array_equal(native_batch.kind, py_batch.kind)
    np.testing.assert_array_equal(native_batch.length, py_batch.length)
    np.testing.assert_array_equal(native_batch.topic_mask, py_batch.topic_mask)
    np.testing.assert_array_equal(native_batch.dest, py_batch.dest)
    np.testing.assert_array_equal(native_batch.valid, py_batch.valid)


def test_push_batch_rejects_oversized_payload_up_front():
    ring = FrameRing(slots=8, frame_bytes=16)
    with pytest.raises(ValueError, match="host path"):
        ring.push_batch([b"ok", b"z" * 17], [5, 5], [1, 1], [-1, -1])
    # nothing was partially packed
    assert ring.free_slots == 8


def test_push_batch_rejects_length_mismatch():
    ring = FrameRing(slots=8, frame_bytes=16)
    with pytest.raises(ValueError, match="mismatch"):
        ring.push_batch([b"a", b"b"], [5], [1, 1], [-1, -1])


def test_push_batch_ring_full_means_requeue():
    ring = FrameRing(slots=2, frame_bytes=16)
    n = ring.push_batch([b"a", b"b", b"c"], [5] * 3, [1] * 3, [-1] * 3)
    assert n == 2  # unambiguous: ring full, re-queue the rest
    batch = ring.take_batch()
    assert batch.num_valid == 2


def test_scan_frames_roundtrip_with_encode():
    payloads = [b"one", b"two two", b"", b"\x00" * 100]
    stream = native.encode_frames(payloads)
    # matches the transport's hand-rolled framing exactly
    expect = b"".join(struct.pack(">I", len(p)) + p for p in payloads)
    assert stream == expect

    frames, consumed, error = native.scan_frames(stream, max_frame_len=1024)
    assert not error
    assert consumed == len(stream)
    assert [stream[o:o + l] for o, l in frames] == payloads


def test_scan_partial_frame_waits():
    stream = native.encode_frames([b"complete"]) + b"\x00\x00\x00\x08part"
    frames, consumed, error = native.scan_frames(stream, max_frame_len=1024)
    assert not error
    assert len(frames) == 1
    assert consumed == len(native.encode_frames([b"complete"]))


def test_scan_flags_oversized_frame():
    stream = struct.pack(">I", 10_000) + b"x" * 10
    frames, consumed, error = native.scan_frames(stream, max_frame_len=1000)
    assert error
    assert frames == []


# ---------------------------------------------------------------------------
# egress engine (pushcdn_egress_count / _fill via native.egress_encode)
# ---------------------------------------------------------------------------

def _egress_reference(deliver, lengths, blocks):
    """Per-user wire streams, the obvious way: concat u32-BE len ‖ payload
    for every delivered frame in frame order."""
    import numpy as np
    U, N = deliver.shape
    rows = blocks[0].shape[0]
    out = {}
    for u in range(U):
        stream = bytearray()
        count = 0
        for n in range(N):
            if deliver[u, n]:
                ln = int(lengths[n])
                payload = bytes(blocks[n // rows][n % rows, :ln])
                stream += struct.pack(">I", ln) + payload
                count += 1
        if count:
            out[u] = (bytes(stream), count)
    return out


def test_egress_encode_matches_reference():
    import numpy as np
    rng = np.random.default_rng(7)
    U, B, S, F = 16, 4, 9, 64  # S*B = 36: exercises the non-multiple-of-8 tail
    blocks = [rng.integers(0, 256, (S, F), dtype=np.uint8) for _ in range(B)]
    N = B * S
    lengths = rng.integers(0, F + 1, N).astype(np.int32)
    deliver = rng.random((U, N)) < 0.3
    deliver[:, lengths == 0] = False  # empty slots never deliver
    streams = native.egress_encode(deliver, lengths, blocks)
    if streams is None:
        pytest.skip("native library unavailable")
    ref = _egress_reference(deliver, lengths, blocks)
    assert sorted(streams.users) == sorted(ref)
    for u in streams.users:
        assert bytes(streams.stream(u)) == ref[u][0]
        assert int(streams.msgs[u]) == ref[u][1]
    assert streams.total_msgs == sum(c for _, c in ref.values())


def test_egress_encode_empty_matrix():
    import numpy as np
    deliver = np.zeros((8, 16), bool)
    lengths = np.zeros(16, np.int32)
    blocks = [np.zeros((8, 32), np.uint8), np.zeros((8, 32), np.uint8)]
    streams = native.egress_encode(deliver, lengths, blocks)
    if streams is None:
        pytest.skip("native library unavailable")
    assert streams.users == []
    assert streams.total_msgs == 0


def test_egress_encode_dense_single_user():
    import numpy as np
    F = 16
    block = np.arange(3 * F, dtype=np.uint8).reshape(3, F)
    lengths = np.array([F, 5, 0], np.int32)
    deliver = np.array([[True, True, False], [False, False, False]])
    streams = native.egress_encode(deliver, lengths, [block])
    if streams is None:
        pytest.skip("native library unavailable")
    assert streams.users == [0]
    expect = (struct.pack(">I", F) + bytes(block[0]) +
              struct.pack(">I", 5) + bytes(block[1, :5]))
    assert bytes(streams.stream(0)) == expect


def test_push_batch_multiword_mask_expansion_and_memo():
    """Multi-word topic masks expand to the exact little-endian u32 words
    through the memoized row cache — uniform, mixed, and out-of-range
    (truncating, matching the old per-word shift loop) mask batches."""
    W = 8
    ring = FrameRing(slots=16, frame_bytes=32, topic_words=W)
    big = (1 << 200) | (1 << 37) | 0b101     # spans words 0, 1, and 6
    over = (1 << (32 * W)) | 0b11            # bit above the topic space
    neg = -1                                 # pathological caller input
    masks = [big, big, over, neg, 0b1]       # uniform run + mixed tail
    n = ring.push_batch([b"m"] * 5, [KIND_BROADCAST] * 5, masks, [-1] * 5)
    assert n == 5
    batch = ring.take_batch()
    allbits = (1 << (32 * W)) - 1
    for i, m in enumerate(masks):
        expect = [(int(m) & allbits) >> (32 * w) & 0xFFFFFFFF
                  for w in range(W)]
        assert list(batch.topic_mask[i]) == expect, (i, m)

    # uniform-mask fast path fills every row identically
    ring2 = FrameRing(slots=16, frame_bytes=32, topic_words=W)
    assert ring2.push_batch([b"u"] * 6, [KIND_BROADCAST] * 6,
                            [big] * 6, [-1] * 6) == 6
    b2 = ring2.take_batch()
    rows = b2.topic_mask[:6]
    assert (rows == rows[0]).all()
    assert list(rows[0]) == [(big >> (32 * w)) & 0xFFFFFFFF
                             for w in range(W)]


def _socket_pairs(n: int):
    import contextlib
    import socket
    stack = contextlib.ExitStack()
    pairs = [socket.socketpair() for _ in range(n)]
    for a, b in pairs:
        stack.callback(a.close)
        stack.callback(b.close)
    return stack, pairs


def test_send_batch_sends_each_entry_once_from_the_shared_buffer():
    """300 entries over the library's threads: every socket gets exactly
    its slice of the buffer, once (an entry handed out twice would show
    as a doubled stream, one skipped as an empty socket)."""
    n, size = 300, 700
    stack, pairs = _socket_pairs(n)
    with stack:
        rng = np.random.default_rng(31)
        buf = bytearray(rng.integers(0, 256, n * size + 64, np.uint8)
                        .tobytes())
        order = rng.permutation(n)
        fds = np.array([pairs[i][0].fileno() for i in order], np.int32)
        offsets = (order * size + 64).astype(np.int64)
        nbytes = np.full(n, size, np.int64)
        nbytes[::7] = 0
        sent = native.send_batch(buf, fds, offsets, nbytes)
        assert sent.dtype == np.int64 and sent.tolist() == nbytes.tolist()
        for k, i in enumerate(order):
            reader = pairs[i][1]
            reader.setblocking(False)
            want = bytes(buf[offsets[k]:offsets[k] + nbytes[k]])
            got = b""
            try:
                got = reader.recv(4 * size)
            except BlockingIOError:
                pass
            assert got == want, int(i)


def test_send_batch_reports_short_sends_and_errnos_per_entry():
    import errno
    import socket
    stack, pairs = _socket_pairs(4)
    with stack:
        for a, _ in pairs:
            a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        buf = bytearray(b"x" * (1 << 20))
        fds = np.array([a.fileno() for a, _ in pairs], np.int32)
        # entry 1: its socket is full
        while native.send_batch(buf, fds[1:2], np.zeros(1, np.int64),
                                np.full(1, 1 << 16, np.int64))[0] > 0:
            pass
        pairs[2][1].close()                 # entry 2: the peer is gone
        pairs[3][0].shutdown(socket.SHUT_WR)
        sent = native.send_batch(buf, fds, np.zeros(4, np.int64),
                                 np.full(4, 1 << 20, np.int64)).tolist()
        assert 0 < sent[0] < 1 << 20        # short: the socket filled
        assert sent[1] in (-errno.EAGAIN, -errno.EWOULDBLOCK)
        assert sent[2:] == [-errno.EPIPE] * 2   # and no SIGPIPE


def test_send_batch_each_sends_each_entrys_own_bytes_once():
    """The variant for entries that own their bytes (a TLS link's sealed
    records): 300 entries of their own lengths over the library's
    threads, each socket gets exactly its object's bytes, once; a full
    socket reads short, a closed peer ``-EPIPE``; anything but one bytes
    object an fd is refused before any send."""
    import errno
    import socket
    n = 300
    stack, pairs = _socket_pairs(n + 1)
    with stack:
        rng = np.random.default_rng(41)
        bufs = [rng.integers(0, 256, int(k), np.uint8).tobytes()
                for k in rng.integers(1, 2000, n)]
        order = rng.permutation(n)
        fds = np.array([pairs[i][0].fileno() for i in order], np.int32)
        sent = native.send_batch_each([bufs[i] for i in order], fds)
        assert sent.dtype == np.int64
        assert sent.tolist() == [len(bufs[i]) for i in order]
        for i in range(n):
            pairs[i][1].setblocking(False)
            assert pairs[i][1].recv(4096) == bufs[i], i
        a, b = pairs[n]
        a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        big = bytes(1 << 20)
        took = native.send_batch_each([big], np.array([a.fileno()]))[0]
        assert 0 < took < len(big)
        b.close()
        assert native.send_batch_each([big], np.array([a.fileno()])).tolist() \
            == [-errno.EPIPE]
        for bad in ([bytearray(4)], [memoryview(b"abcd")], [b"a", b"b"]):
            with pytest.raises(ValueError):
                native.send_batch_each(bad, np.array([a.fileno()]))


def test_send_batch_refuses_streams_outside_the_buffer():
    stack, pairs = _socket_pairs(1)
    with stack:
        fds = np.array([pairs[0][0].fileno()], np.int32)
        buf = bytearray(100)
        for off, n in ((90, 11), (-1, 5), (0, -1)):
            with pytest.raises(ValueError):
                native.send_batch(buf, fds, np.array([off], np.int64),
                                  np.array([n], np.int64))
        with pytest.raises(ValueError):
            native.send_batch(buf, fds, np.zeros(2, np.int64),
                              np.zeros(2, np.int64))
        assert native.send_batch(buf, fds, np.array([90], np.int64),
                                 np.array([10], np.int64)).tolist() == [10]
