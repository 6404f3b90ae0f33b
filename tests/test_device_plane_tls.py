"""A device-plane broker whose users come over TCP+TLS (ISSUE 39): the
deployment of ``prod1-1k`` at 16 users, Ed25519 keys (the signature scheme
never reaches the data path), real sockets.

The same seeded traffic through the same broker over ``tcp+tls`` and over
plain ``tcp`` has to give every user the same sequence of every
(publisher, stream), and that sequence is what the benchmark's plain
reference owes: across a subscriber that stops reading until its link
refuses the pump's write and then reads again, with a per-user stream
longer than one TLS record, and with a user that goes away while a step is
in flight. The encrypted leg's four counters and ``plane.egress``'s
``tls`` and ``tls_batched`` are held to the hand-offs there were, on a
real trace too.

Since ISSUE 40 a back-pressured step seals each idle TLS user's stream on
the loop with that link's own ``SSLObject`` and sends the records in the
native batch (``Connection.seal_idle``, ``native.send_batch_each``): the
same comparisons hold with every such send cut short, and a link with a
record or a byte held back is not sealed."""

import asyncio
import errno
import os
import socket
import ssl
import struct
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import reference  # noqa: E402
from benchmark.loadgen import plan  # noqa: E402
from benchmark.loadgen.gaps import GapDetector  # noqa: E402
from pushcdn_tpu.testing import wait_until  # noqa: E402
from tests.test_device_plane import _served_over_tcp  # noqa: E402

USERS, TOPICS, PUBLISHERS = 16, 4, 4
# ``benchmark/traffic/fanout4-sat.json`` at a small size: four subscribers a
# topic, broadcasts on a uniform draw of the topics, directs within the one
# placement group, a probe to self now and then
FLOW = {"name": "saturate", "publishers": PUBLISHERS,
        "loop": {"kind": "windowed", "window": 64, "probe_every": 16,
                 "probe_bytes": 64},
        "mix": [{"share": 0.9, "kind": "broadcast", "bytes": 900,
                 "topic": {"uniform": TOPICS}},
                {"share": 0.1, "kind": "direct", "bytes": 256,
                 "to": {"group_offset": 0}}]}
SUBSCRIPTIONS = [{"users": "all", "topic": {"mod": TOPICS}}]
TLS_RECORD = 16 * 1024
COUNTERS = ("egress_tls", "egress_tls_inline", "egress_tls_write_us",
            "egress_tls_batched")
# ``Served.how``'s code for a hand-off the native batch took (the one-by-one
# path's are ``senders.INLINE`` and ``senders.QUEUED``, 1 and 2)
BATCHED = 3


def _plane(ring_slots=16):
    """A lane of 16: four publishers fill it at every take, so the steps
    are back-pressured and plain links would leave in the native batch."""
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    return DevicePlaneConfig(num_user_slots=32, ring_slots=ring_slots,
                             frame_bytes=1024, batch_window_s=0.002,
                             bypass_max_items=0)


class Served:
    """One ``DevicePlane`` broker with ``USERS`` users over ``transport``,
    subscribed as ``SUBSCRIPTIONS`` says. Every client drains into a gap
    detector of its own and into ``got[user][(publisher, stream)]``, the
    sequence numbers in the order they came; ``how[user]`` is how each of
    its stream hand-offs went (``INLINE``, ``QUEUED`` or ``BATCHED``), with
    the stream's length, and ``shorts[user]`` what each of its batched
    sealed sends took of how many bytes where that was not all. ``cut``,
    if given, rewrites the batched sealed sends: ``cut(records)`` is how
    many bytes of them the socket is offered (0: ``EAGAIN``), the rest
    then goes the way of a short send."""

    def __init__(self, transport, seed, monkeypatch, ring_slots=16,
                 cut=None):
        self.transport, self.seed = transport, seed
        self.layout = plan.Layout(USERS, 1, 1, 1, [FLOW])
        self.table = plan.subscriptions(SUBSCRIPTIONS, USERS)
        self.detectors = [GapDetector() for _ in range(USERS)]
        self.got = [{} for _ in range(USERS)]
        self.how = [[] for _ in range(USERS)]
        self.shorts = [[] for _ in range(USERS)]
        self.foreign = 0
        self.sequences = {}
        self.log = []
        self._plans = {}
        from pushcdn_tpu.proto.topic import TopicSpace
        from pushcdn_tpu.proto.transport import Tcp, TcpTls
        self._served = _served_over_tcp(
            39_000 + 100 * seed, _plane(ring_slots), self.table,
            protocol={"tcp": Tcp, "tcp+tls": TcpTls}[transport],
            topic_space=TopicSpace.range(TOPICS))
        self._watch_handoffs(monkeypatch, cut)

    def _watch_handoffs(self, monkeypatch, cut):
        from pushcdn_tpu import native
        from pushcdn_tpu.broker.tasks import senders
        real = senders.try_send_encoded_to_user_nowait
        real_settle, real_send = senders._send_and_settle, \
            native.send_batch_each

        def watched(plane, broker, key, data, **kwargs):
            how = real(plane, broker, key, data, **kwargs)
            if how and key in self.user_of:
                self.how[self.user_of[key]].append((how, len(data)))
            return how

        def settle(plane, broker, streams, batch):
            users = [self.user_of.get(key) for key in batch.keys]
            for user, slot in zip(users, batch.slots):
                if user is not None and batch.records:
                    self.how[user].append((BATCHED, int(streams.nbytes[slot])))
            self._batch_users = users
            return real_settle(plane, broker, streams, batch)

        def send_batch_each(bufs, fds):
            offered = bufs if cut is None else [
                records[:cut(records)] for records in bufs]
            sent = real_send([b for b in offered if b], fds[[
                i for i, b in enumerate(offered) if b]])
            sent = iter(sent.tolist())
            out = np.array([next(sent) if b else -errno.EAGAIN
                            for b in offered], np.int64)
            for user, records, took in zip(self._batch_users, bufs, out):
                if user is not None and took != len(records):
                    self.shorts[user].append((int(took), len(records)))
            return out
        self.user_of, self._batch_users = {}, []
        monkeypatch.setattr(senders, "try_send_encoded_to_user_nowait",
                            watched)
        monkeypatch.setattr(senders, "_send_and_settle", settle)
        monkeypatch.setattr(native, "send_batch_each", send_batch_each)

    async def __aenter__(self):
        self.broker, self.clients = await self._served.__aenter__()
        self.plane = self.broker.device_plane
        self.user_of.update(
            (c.public_key, u) for u, c in enumerate(self.clients))
        self.drains = [asyncio.create_task(self._drain(user))
                       for user in range(USERS)]
        return self

    async def __aexit__(self, *exc):
        for task in self.drains:
            task.cancel()
        return await self._served.__aexit__(*exc)

    async def _drain(self, user):
        from pushcdn_tpu.proto.message import Broadcast
        while True:
            for message in await self.clients[user].receive_messages():
                publisher, stream, seq, _due, target = plan.HEADER.unpack_from(
                    bytes(message.message))
                mine = (target in self.table[user]
                        if isinstance(message, Broadcast) else target == user)
                self.foreign += not mine
                self.detectors[user].observe(publisher, stream, seq)
                self.got[user].setdefault((publisher, stream), []).append(seq)

    def link(self, user):
        """The broker's end of ``user``'s link."""
        return self.broker.connections.get_user_connection(
            self.clients[user].public_key)

    async def publish(self, publisher, count, frames=None):
        """The publisher's next ``count`` frames of the seeded plan (or
        ``frames``), written back to back: consecutive sends pipeline into
        few writes, so the receive batches are long and the lane fills."""
        if frames is None:
            source = self._plans.setdefault(publisher, plan.frame_plan(
                self.seed, self.layout, FLOW, publisher))
            frames = [next(source) for _ in range(count)]
        pool = plan.make_pool(self.seed)
        client = self.clients[self.layout.pub_users[publisher]]
        sends = []
        for frame in frames:
            key = (publisher, plan.stream_of(frame), frame.target)
            seq = self.sequences.get(key, 0)
            self.sequences[key] = seq + 1
            payload = plan.build_payload(pool, publisher, frame, seq, 0)
            self.log.append((publisher, frame.kind, frame.target))
            sends.append(
                client.send_broadcast_message([frame.target], payload)
                if frame.kind == plan.BROADCAST else
                client.send_direct_message(
                    self.clients[frame.target].public_key, payload))
        await asyncio.gather(*sends)

    def owed(self):
        return reference.route(self.table, self.log)

    def received(self, users=range(USERS)):
        return sum(state.unique for user in users
                   for state in self.detectors[user].streams.values())

    async def settle(self, users=range(USERS), timeout=30):
        """Until ``users`` hold all the reference owes them, and a moment
        more (a duplicate would land right behind)."""
        owed = self.owed()
        want = sum(sum(owed[user].values()) for user in users)
        await wait_until(lambda: self.received(users) >= want, timeout)
        await asyncio.sleep(0.05)

    def problems(self, users=range(USERS)):
        """What differs from the plain reference, as text: a count, a
        gap, a reorder, a duplicate, a foreign delivery."""
        owed = self.owed()
        reports = [d.report() for d in self.detectors]
        bad = reference.compare([owed[u] for u in users],
                                [reports[u] for u in users])
        dups = sum(state.duplicates for user in users
                   for state in self.detectors[user].streams.values())
        return bad + ([f"{dups} duplicates"] if dups else []) \
            + ([f"{self.foreign} foreign"] if self.foreign else [])


def _identities(served):
    """The four counters against the hand-offs there were: all of a TLS
    broker's are over an encrypting stream, inline ones by the pump's own
    write or in the native batch with their records (``egress_tls_batched``,
    never ``egress_batched``, which the benchmark's tests of a TLS
    deployment hold at 0); a plain broker's read 0, and no clock ran for
    them."""
    plane, said = served.plane, served.plane.describe()
    assert set(COUNTERS) <= set(said)
    assert said["egress_tls"] == plane.egress_tls
    assert said["egress_tls_inline"] == plane.egress_tls_inline
    assert said["egress_tls_write_us"] == plane.egress_tls_write_ns // 1000
    assert said["egress_tls_batched"] == plane.egress_tls_batched
    hows = [how for user in served.how for how, _n in user]
    if served.transport == "tcp+tls":
        assert plane.egress_tls == plane.egress_inline + plane.egress_queued \
            == len(hows) > 0
        assert plane.egress_tls_inline == plane.egress_inline == \
            hows.count(1) + hows.count(BATCHED)
        assert plane.egress_tls_batched == hows.count(BATCHED)
        assert plane.egress_queued == hows.count(2)
        assert plane.egress_batched == plane.egress_batched_short == 0
        assert plane.egress_tls_write_ns > 0
        assert all(served.link(u).encrypts and served.link(u).idle_fd(1)
                   is None for u in range(USERS))
    else:
        assert (plane.egress_tls, plane.egress_tls_inline,
                plane.egress_tls_write_ns, plane.egress_tls_batched) == \
            (0, 0, 0, 0)
        assert plane.egress_inline + plane.egress_queued == \
            len(hows) + plane.egress_batched
        assert not any(served.link(u).encrypts for u in range(USERS))


async def _seeded_traffic(transport, seed, monkeypatch, cut=None):
    """Three rounds of 48 frames a publisher through one broker; what
    every user received, in order, and the broker's plane."""
    async with Served(transport, seed, monkeypatch, cut=cut) as served:
        for _round in range(3):
            await asyncio.gather(*(served.publish(p, 48)
                                   for p in range(PUBLISHERS)))
        await served.settle()
        assert served.problems() == []
        assert served.received() == reference.total(served.owed())
        assert served.broker.connections.num_users == USERS
        assert not served.plane.disabled and served.plane.steps >= 3
        _identities(served)
        return served.got, served.owed(), served.plane, served.shorts


def _same_over_both(over_tls, owed_tls, over_tcp, owed_tcp):
    # one seed, one plan: both brokers were offered the same frames
    assert owed_tls == owed_tcp
    # every user, every (publisher, stream): the same sequence over both
    # transports, and it counts from 0 without a gap (FIFO, nothing lost
    # or doubled); a stream's length is what the reference owes
    assert over_tls == over_tcp
    for user, streams in enumerate(over_tls):
        assert {key: len(seqs) for key, seqs in streams.items()} == \
            owed_tls[user]
        for seqs in streams.values():
            assert seqs == list(range(len(seqs)))


@pytest.mark.parametrize("seed", [1, 2, 3])
async def test_tls_users_get_what_plain_users_get_and_the_reference_owes(
        seed, monkeypatch):
    over_tls, owed_tls, tls_plane, shorts = await _seeded_traffic(
        "tcp+tls", seed, monkeypatch)
    over_tcp, owed_tcp, tcp_plane, _ = await _seeded_traffic(
        "tcp", seed, monkeypatch)
    _same_over_both(over_tls, owed_tls, over_tcp, owed_tcp)
    # the lane of 16 was full at the takes: plain links left in the
    # native batch, and so did the TLS links, sealed on the loop
    assert tcp_plane.egress_batched > 0
    assert tls_plane.egress_tls_batched > 0 == tls_plane.egress_batched
    assert not any(shorts)  # the readers kept up


@pytest.mark.parametrize("seed", [4, 5, 6])
async def test_tls_users_get_it_all_when_every_batched_send_falls_short(
        seed, monkeypatch):
    """The same comparison with every batched sealed send cut: a third
    of the sends offer the socket nothing (``EAGAIN``), the others part of
    their records, down to a byte inside a record's header; the rest of
    each goes to the TCP transport beneath the record layer, never
    through it again, so the client decrypts every byte of it."""
    cuts = iter(range(1 << 30))

    def cut(records):
        k = next(cuts) % 3
        return 0 if k == 0 else 3 if k == 1 else len(records) // 2
    over_tls, owed_tls, tls_plane, shorts = await _seeded_traffic(
        "tcp+tls", seed, monkeypatch, cut=cut)
    over_tcp, owed_tcp, _tcp_plane, _ = await _seeded_traffic(
        "tcp", seed, monkeypatch)
    _same_over_both(over_tls, owed_tls, over_tcp, owed_tcp)
    assert tls_plane.egress_tls_batched == sum(map(len, shorts)) > 0
    taken = [took for user in shorts for took, _n in user]
    assert taken.count(-errno.EAGAIN) > 0 and taken.count(3) > 0


async def test_a_tls_subscriber_that_stops_reading_is_queued_then_inline_again(
        monkeypatch):
    """The slow reader's link takes the native batch's sends of its
    sealed records until they come back short, whose rest goes to the TCP
    transport beneath the record layer; while that holds bytes the link is
    not sealed, the pump writes to it itself (inline) until the transport
    holds more than its low-water mark, and then every hand-off is queued for
    its writer; once it reads again and the writer has caught up, the
    pump writes it itself again. The client decrypts every byte (no bad
    record MAC) and nothing is lost, doubled or reordered across the
    changes, and nobody is removed."""
    slow = 5
    async with Served("tcp+tls", 11, monkeypatch) as served:
        reader = served.clients[slow]._connection._stream
        # small socket buffers, so that the link backs up within the
        # traffic; the records already in flight stay readable
        reader.writer.get_extra_info("socket").setsockopt(
            socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        served.link(slow)._stream.writer.get_extra_info("socket").setsockopt(
            socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        reader.reader._transport.pause_reading()
        fast = [u for u in range(USERS) if u != slow]
        # every publisher's broadcasts on the slow reader's topic: ~0.9 KB
        # a frame into its one link, past asyncio's 64 KiB of buffer
        topic = min(served.table[slow])
        burst = [plan.Frame(plan.BROADCAST, topic, 900)] * 48
        for _round in range(4):
            await asyncio.gather(*(served.publish(p, 0, burst)
                                   for p in range(PUBLISHERS)))
            await served.settle(fast)
        hows = [how for how, _n in served.how[slow]]
        assert hows[0] == BATCHED and hows[-1] == 2, hows
        # batched sends came back short (their rest went to the TCP
        # transport), before any hand-off was queued
        assert served.shorts[slow], hows
        queued_at = hows.index(2)
        assert all(how == 2 for how in hows[queued_at:]), hows
        assert served.plane.egress_queued == len(hows) - queued_at
        reader.reader._transport.resume_reading()
        await served.settle()
        # the writer has drained its queue: the link is idle again
        await wait_until(lambda: served.link(slow)._send_q.empty()
                         and not served.link(slow)._write_mutex.locked())
        await asyncio.gather(*(served.publish(p, 0, burst[:8])
                               for p in range(PUBLISHERS)))
        await served.settle()
        assert served.how[slow][-1][0] in (1, BATCHED), served.how[slow][-4:]
        assert served.problems() == []
        assert served.broker.connections.num_users == USERS
        _identities(served)


async def test_a_stream_longer_than_one_tls_record_arrives_whole_and_in_order(
        monkeypatch):
    """A step's stream for one user over 16 KiB leaves in one inline
    write and crosses as several records."""
    async with Served("tcp+tls", 12, monkeypatch, ring_slots=64) as served:
        frames = [plan.Frame(plan.BROADCAST, 0, 1000)] * 40
        await served.publish(0, 0, frames)
        await served.settle()
        subscribers = [u for u in range(USERS) if 0 in served.table[u]]
        for user in subscribers:
            assert max(n for _how, n in served.how[user]) > TLS_RECORD
            assert served.got[user][(0, 0)] == list(range(40))
        assert served.problems() == []
        assert served.plane.egress_queued == 0
        _identities(served)


async def test_a_tls_user_that_goes_away_mid_step_is_removed_alone(
        monkeypatch):
    """The victim's connection is torn down while a step that owes it
    deliveries is on the worker thread: the broker removes that user,
    the step's egress drops what it was owed, every other user gets all
    of theirs, and the plane stays up."""
    victim = 5   # subscribed to topic 1, no publisher
    async with Served("tcp+tls", 13, monkeypatch) as served:
        plane, loop = served.plane, asyncio.get_running_loop()
        await served.publish(1, 16)
        await served.settle()
        real, armed = plane._run_step, [True]

        def run_step(*args, **kwargs):
            jobs = real(*args, **kwargs)
            if armed:
                armed.clear()
                loop.call_soon_threadsafe(served.clients[victim].close)
                deadline = time.monotonic() + 10
                while served.broker.connections.num_users == USERS \
                        and time.monotonic() < deadline:
                    time.sleep(0.005)
            return jobs
        monkeypatch.setattr(plane, "_run_step", run_step)
        handed = len(served.how[victim])
        await served.publish(0, 0, [plan.Frame(plan.BROADCAST, 1, 900)] * 16)
        others = [u for u in range(USERS) if u != victim]
        await served.settle(others)
        assert not armed
        assert served.broker.connections.num_users == USERS - 1
        assert served.link(victim) is None
        assert len(served.how[victim]) == handed   # nothing handed to it
        # the step after: fifteen users, as if the victim had never been
        await asyncio.gather(*(served.publish(p, 32)
                               for p in range(PUBLISHERS) if
                               served.layout.pub_users[p] != victim))
        await served.settle(others)
        assert served.problems(others) == []
        assert not plane.disabled
        plane_said = plane.describe()
        assert plane_said["egress_tls"] == \
            plane.egress_inline + plane.egress_queued
        assert plane_said["egress_tls_batched"] > 0 == plane.egress_batched


async def test_traced_egress_reports_the_encrypted_hand_offs(monkeypatch,
                                                             tmp_path):
    """On a real trace ``plane.egress``'s ``tls`` sums to what
    ``egress_tls`` moved by, with ``inline`` + ``queued``, and the
    program's spans stay flat with the clock in the hand-off."""
    import jax

    from pushcdn_tpu.parallel import spans
    from tests.test_plane_spans import INGRESS, PLANE, _program_spans
    spans.bind()  # what runtime.init does in a device-owning process
    async with Served("tcp+tls", 14, monkeypatch) as served:
        plane = served.plane
        await served.publish(0, 16)       # untraced: records nothing
        await served.settle()
        before = plane.describe()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            for _round in range(3):
                await asyncio.gather(*(served.publish(p, 32)
                                       for p in range(PUBLISHERS)))
                await served.settle()
        finally:
            jax.profiler.stop_trace()
        after = plane.describe()
        assert served.problems() == []
        _identities(served)
    threads, _trace_ns = _program_spans(str(tmp_path))
    events = [e for evs in threads.values() for e in evs]
    assert {e[0] for e in events} == set(PLANE + INGRESS)
    # flat: on one thread no two of the program's spans overlap
    for evs in threads.values():
        evs.sort(key=lambda e: e[1])
        for (a, _s, a_end, _), (b, b_start, _e, _) in zip(evs, evs[1:]):
            assert a_end <= b_start, (a, b)

    def total(stat):
        return sum(e[3][stat] for e in events if e[0] == "plane.egress")
    moved = {key: after[key] - before[key] for key in (
        "egress_tls", "egress_tls_inline", "egress_inline", "egress_queued",
        "egress_batched", "egress_tls_batched", "egress_tls_write_us",
        "pump_egress_us")}
    assert total("tls") == moved["egress_tls"] > 0
    assert total("tls") == total("inline") + total("queued")
    assert total("inline") == moved["egress_inline"] == \
        moved["egress_tls_inline"]
    assert total("batched") == moved["egress_batched"] == 0
    # the sealed sends: in the native batch on the back-pressured steps,
    # counted apart from the plain links' ``batched``
    assert 0 < total("tls_batched") == moved["egress_tls_batched"] \
        <= total("inline")
    assert all(e[3]["tls_batched"] <= e[3]["tls"] for e in events
               if e[0] == "plane.egress")
    # the timed writes lie inside the pump's egress state
    assert 0 < moved["egress_tls_write_us"] <= moved["pump_egress_us"]


# ---------------------------------------------------------------------------
# ``Connection.seal_idle`` on one real TLS link (ISSUE 40): what it reads of
# asyncio's SSL protocol, and the states in which it must not seal.
# ---------------------------------------------------------------------------

class _Link:
    """One TCP+TLS link: the listener's end (``server``, which seals) and
    the dialer's (``client``, which reads)."""

    async def __aenter__(self):
        from pushcdn_tpu.proto.transport import TcpTls
        self.listener = await TcpTls.bind("127.0.0.1:0")
        dial = asyncio.create_task(
            TcpTls.connect(f"127.0.0.1:{self.listener.bound_port}"))
        self.server = await (await self.listener.accept()).finalize()
        self.client = await dial
        self.sent = []
        return self

    async def __aexit__(self, *exc):
        self.server.close()
        self.client.close()
        await self.listener.close()

    def frame(self, size=900):
        """The next length-delimited frame, its payload numbered."""
        payload = struct.pack(">I", len(self.sent)) + bytes(size - 4)
        self.sent.append(payload)
        return memoryview(struct.pack(">I", size) + payload)

    def send_sealed(self, data):
        """Seal ``data`` and send the records as the batch does; False
        where the link would not seal."""
        from pushcdn_tpu import native
        got = self.server.seal_idle(data)
        if got is None:
            return False
        fd, records = got
        sent = native.send_batch_each([records], np.array([fd], np.int32))
        self.server.sent_on_fd(data, int(sent[0]), records=records)
        return True

    async def received(self, n, timeout=10):
        async with asyncio.timeout(timeout):
            return [bytes((await self.client.recv_raw()).data)
                    for _ in range(n)]


async def test_an_encrypting_stream_resolves_what_its_seal_reads():
    """On the pinned interpreter the fast path is live: an encrypting
    ``AsyncioStream`` finds asyncio's ``SSLProtocol``, its ``SSLObject``
    (the public ``ssl_object``), its outgoing BIO and the TCP transport
    beneath, and a sealed frame arrives whole; a plain stream resolves
    nothing and gives no records."""
    from asyncio import sslproto
    async with _Link() as link:
        stream = link.server._stream
        assert link.server.encrypts and stream._seal is not None
        protocol, ssl_object, outgoing, tcp, fd = stream._seal
        assert isinstance(protocol, sslproto.SSLProtocol)
        assert ssl_object is stream.writer.get_extra_info("ssl_object")
        assert isinstance(ssl_object, ssl.SSLObject)
        assert ssl_object.version() == "TLSv1.3"
        assert isinstance(outgoing, ssl.MemoryBIO)
        assert tcp is protocol._transport and not tcp.is_closing()
        assert fd == tcp.get_extra_info("socket").fileno() >= 0
        assert link.server.idle_fd(1) is None
        assert link.send_sealed(link.frame()) and link.send_sealed(
            link.frame(20_000))  # two records
        assert await link.received(2) == link.sent
    from pushcdn_tpu.proto.transport import Tcp
    listener = await Tcp.bind("127.0.0.1:0")
    try:
        dial = asyncio.create_task(
            Tcp.connect(f"127.0.0.1:{listener.bound_port}"))
        server = await (await listener.accept()).finalize()
        client = await dial
        assert server._stream._seal is None and not server.encrypts
        assert server.seal_idle(memoryview(b"\0\0\0\0")) is None
        server.close()
        client.close()
    finally:
        await listener.close()


@pytest.mark.parametrize("held", ["record_in_the_bio", "bytes_in_the_tcp"])
async def test_a_link_that_holds_something_back_is_not_sealed(held):
    """A record waiting in the SSL protocol's outgoing BIO (a handshake
    message, ticket or KeyUpdate would wait there; here a frame the record
    layer sealed that nobody has read off), or bytes the TCP transport
    holds, must leave before anything sealed after them: the link is not
    sealed, the hand-off takes the one-by-one path, and every frame
    arrives, in order."""
    async with _Link() as link:
        protocol, ssl_object, outgoing, tcp, _fd = link.server._stream._seal
        assert link.send_sealed(link.frame())
        if held == "record_in_the_bio":
            ssl_object.write(link.frame())
            assert outgoing.pending
        else:
            # the reader stops and the socket fills: the transport keeps
            # what the kernel would not take
            link.client._stream.reader._transport.pause_reading()
            tcp.get_extra_info("socket").setsockopt(
                socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
            while not tcp.get_write_buffer_size():
                assert link.server.try_send_encoded_inline(link.frame(16_000))
                await asyncio.sleep(0)
        assert link.server.seal_idle(link.frame()) is None
        link.sent.pop()  # never sealed: the record layer has not seen it
        assert link.server.try_send_encoded_inline(link.frame())
        if held == "bytes_in_the_tcp":
            link.client._stream.reader._transport.resume_reading()
        assert await link.received(len(link.sent)) == link.sent
        # all out, nothing held: the link seals again
        await wait_until(lambda: not tcp.get_write_buffer_size())
        assert link.send_sealed(link.frame())
        assert await link.received(1) == link.sent[-1:]
