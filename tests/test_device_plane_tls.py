"""A device-plane broker whose users come over TCP+TLS (ISSUE 39): the
deployment of ``prod1-1k`` at 16 users, Ed25519 keys (the signature scheme
never reaches the data path), real sockets.

The same seeded traffic through the same broker over ``tcp+tls`` and over
plain ``tcp`` has to give every user the same sequence of every
(publisher, stream), and that sequence is what the benchmark's plain
reference owes: across a subscriber that stops reading until its link
refuses the pump's write and then reads again, with a per-user stream
longer than one TLS record, and with a user that goes away while a step is
in flight. The encrypted leg's three counters and ``plane.egress``'s
``tls`` are held to the hand-offs there were, on a real trace too."""

import asyncio
import os
import socket
import sys
import time

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
COUNTERS = ("egress_tls", "egress_tls_inline", "egress_tls_write_us")


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
    its one-by-one stream hand-offs went, with the stream's length."""

    def __init__(self, transport, seed, monkeypatch, ring_slots=16):
        self.transport, self.seed = transport, seed
        self.layout = plan.Layout(USERS, 1, 1, 1, [FLOW])
        self.table = plan.subscriptions(SUBSCRIPTIONS, USERS)
        self.detectors = [GapDetector() for _ in range(USERS)]
        self.got = [{} for _ in range(USERS)]
        self.how = [[] for _ in range(USERS)]
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
        self._watch_handoffs(monkeypatch)

    def _watch_handoffs(self, monkeypatch):
        from pushcdn_tpu.broker.tasks import senders
        real = senders.try_send_encoded_to_user_nowait

        def watched(plane, broker, key, data, **kwargs):
            how = real(plane, broker, key, data, **kwargs)
            if how and key in self.user_of:
                self.how[self.user_of[key]].append((how, len(data)))
            return how
        self.user_of = {}
        monkeypatch.setattr(senders, "try_send_encoded_to_user_nowait",
                            watched)

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
    """The three counters against the hand-offs there were: all of a TLS
    broker's are over an encrypting stream and none leaves in the native
    batch; a plain broker's read 0, and no clock ran for them."""
    plane, said = served.plane, served.plane.describe()
    assert set(COUNTERS) <= set(said)
    assert said["egress_tls"] == plane.egress_tls
    assert said["egress_tls_inline"] == plane.egress_tls_inline
    assert said["egress_tls_write_us"] == plane.egress_tls_write_ns // 1000
    handed = sum(map(len, served.how))
    if served.transport == "tcp+tls":
        assert plane.egress_tls == plane.egress_inline + plane.egress_queued \
            == handed > 0
        assert plane.egress_tls_inline == plane.egress_inline == sum(
            how == 1 for user in served.how for how, _n in user)
        assert plane.egress_batched == plane.egress_batched_short == 0
        assert plane.egress_tls_write_ns > 0
        assert all(served.link(u).encrypts and served.link(u).idle_fd(1)
                   is None for u in range(USERS))
    else:
        assert (plane.egress_tls, plane.egress_tls_inline,
                plane.egress_tls_write_ns) == (0, 0, 0)
        assert plane.egress_inline + plane.egress_queued == \
            handed + plane.egress_batched
        assert not any(served.link(u).encrypts for u in range(USERS))


async def _seeded_traffic(transport, seed, monkeypatch):
    """Three rounds of 48 frames a publisher through one broker; what
    every user received, in order, and the broker's plane."""
    async with Served(transport, seed, monkeypatch) as served:
        for _round in range(3):
            await asyncio.gather(*(served.publish(p, 48)
                                   for p in range(PUBLISHERS)))
        await served.settle()
        assert served.problems() == []
        assert served.received() == reference.total(served.owed())
        assert served.broker.connections.num_users == USERS
        assert not served.plane.disabled and served.plane.steps >= 3
        _identities(served)
        return served.got, served.owed(), served.plane


@pytest.mark.parametrize("seed", [1, 2, 3])
async def test_tls_users_get_what_plain_users_get_and_the_reference_owes(
        seed, monkeypatch):
    over_tls, owed_tls, tls_plane = await _seeded_traffic(
        "tcp+tls", seed, monkeypatch)
    over_tcp, owed_tcp, tcp_plane = await _seeded_traffic(
        "tcp", seed, monkeypatch)
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
    # the lane of 16 was full at the takes: plain links left in the
    # native batch, which no TLS link can
    assert tcp_plane.egress_batched > 0 and tls_plane.egress_batched == 0


async def test_a_tls_subscriber_that_stops_reading_is_queued_then_inline_again(
        monkeypatch):
    """The slow reader's link takes the pump's writes (inline) until the
    transport holds more than its low-water mark, then every hand-off is
    queued for its writer; once it reads again and the writer has caught
    up, the pump writes it itself again. Nothing is lost, doubled or
    reordered across the two changes, and nobody is removed."""
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
        assert hows[0] == 1 and hows[-1] == 2, hows
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
        assert served.how[slow][-1][0] == 1, served.how[slow][-4:]
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
        assert plane.egress_batched == 0


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
        "egress_batched", "egress_tls_write_us", "pump_egress_us")}
    assert total("tls") == moved["egress_tls"] > 0
    assert total("tls") == total("inline") + total("queued")
    assert total("inline") == moved["egress_inline"] == \
        moved["egress_tls_inline"]
    assert total("batched") == moved["egress_batched"] == 0
    # the timed writes lie inside the pump's egress state
    assert 0 < moved["egress_tls_write_us"] <= moved["pump_egress_us"]
