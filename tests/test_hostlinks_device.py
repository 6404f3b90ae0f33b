"""Device-plane brokers meshed over host links (ISSUE 35): two and four
``Broker``s with a ``DevicePlane`` each, in one process over the Memory
transport, users placed a group a broker, ``cross-sat``'s shape at a
small size, held to the benchmark's plain reference stream by stream
(counts, order, no duplicate, no foreign delivery) with rings small enough
that a peer's link meets a full ring; what the program does with a frame
published before interest has crossed; and the ``links.*`` spans against
the plane's link counters on a real trace."""

import asyncio
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import reference  # noqa: E402
from benchmark.loadgen import plan  # noqa: E402
from benchmark.loadgen.gaps import GapDetector  # noqa: E402
from pushcdn_tpu.testing import Cluster, wait_until  # noqa: E402

USERS, TOPICS, PUBLISHERS = 16, 4, 4
# ``benchmark/traffic/cross-sat.json`` at a small size: one subscriber of
# each topic behind every broker, directs to a user behind the next one
FLOW = {"name": "saturate", "publishers": PUBLISHERS,
        "loop": {"kind": "windowed", "window": 64, "probe_every": 16,
                 "probe_bytes": 64},
        "mix": [{"share": 0.9, "kind": "broadcast", "bytes": 200,
                 "topic": {"uniform": TOPICS}},
                {"share": 0.1, "kind": "direct", "bytes": 64,
                 "to": {"group_offset": 1}}]}
SUBSCRIPTIONS = [{"users": "all", "topic": {"mod": TOPICS}}]
LINKS = ("links.scan", "links.stage", "links.forward")


def _plane(ring_slots, **more):
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    return DevicePlaneConfig(num_user_slots=32, ring_slots=ring_slots,
                             frame_bytes=1024, batch_window_s=0.002, **more)


class Mesh:
    """``brokers`` meshed device-plane brokers with ``USERS`` clients,
    user ``u`` behind broker ``layout.group_of(u)`` and subscribed as
    ``SUBSCRIPTIONS`` says; every client drains into a detector of its
    own, which also tells a delivery that was not meant for it."""

    def __init__(self, brokers, plane, seed):
        self.n, self.plane, self.seed = brokers, plane, seed
        self.layout = plan.Layout(USERS, brokers, 1, 1, [FLOW])
        self.table = plan.subscriptions(SUBSCRIPTIONS, USERS)
        self.detectors = [GapDetector() for _ in range(USERS)]
        self.foreign = 0
        self.sequences = {}

    async def __aenter__(self):
        from pushcdn_tpu.proto.topic import TopicSpace
        self.cluster = await Cluster(
            num_brokers=self.n, device_plane=self.plane,
            topics=TopicSpace.range(TOPICS + 1)).start()
        await wait_until(lambda: all(
            b.connections.num_brokers == self.n - 1
            for b in self.cluster.brokers))
        self.clients = []
        for user in range(USERS):
            await self.cluster.place_on(self.layout.group_of(user))
            client = self.cluster.client(seed=35_000 + self.seed * 100 + user,
                                         topics=sorted(self.table[user]))
            await client.ensure_initialized()
            self.clients.append(client)
        brokers = self.cluster.brokers
        await wait_until(lambda: all(
            b.connections.num_users == USERS // self.n for b in brokers))
        # interest has crossed: every broker knows every user's home and
        # every peer's topics (the benchmark's launcher waits for the same)
        await wait_until(lambda: all(
            len(b.connections.direct_map) == USERS and all(
                len(b.connections.broker_topics.get_values_of_key(str(
                    p.identity))) == TOPICS for p in brokers if p is not b)
            for b in brokers))
        self.drains = [asyncio.create_task(self._drain(user))
                       for user in range(USERS)]
        return self

    async def __aexit__(self, *exc):
        for task in self.drains:
            task.cancel()
        for client in self.clients:
            client.close()
        await self.cluster.stop()

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

    def frames_of(self, publisher, count):
        """The publisher's next ``count`` frames as the generator plans
        them, each with its payload."""
        frames = plan.frame_plan(self.seed, self.layout, FLOW, publisher)
        pool = plan.make_pool(self.seed)
        out = []
        for frame in (next(frames) for _ in range(count)):
            key = (publisher, plan.stream_of(frame), frame.target)
            seq = self.sequences.get(key, 0)
            self.sequences[key] = seq + 1
            out.append((frame, plan.build_payload(pool, publisher, frame,
                                                  seq, 0)))
        return out

    async def publish(self, publisher, planned):
        """Back to back: consecutive sends pipeline into few writes, so
        the broker's receive batches are long and the plane engages."""
        client = self.clients[self.layout.pub_users[publisher]]
        await asyncio.gather(*(
            client.send_broadcast_message([frame.target], payload)
            if frame.kind == plan.BROADCAST else
            client.send_direct_message(
                self.clients[frame.target].public_key, payload)
            for frame, payload in planned))

    def received(self):
        return sum(state.unique for detector in self.detectors
                   for state in detector.streams.values())

    def duplicates(self):
        return sum(state.duplicates for detector in self.detectors
                   for state in detector.streams.values())


def _count_full_rings_on_links(monkeypatch, mesh):
    """How often a peer's link (and a user's own) met a full ring: the
    retry that blocks the reader, told apart by where the frame's
    publisher lives. A peer's link hands each such frame to
    ``_stage_with_backpressure``; a user loop hands its batch's to
    ``_retry_full`` (which stages the native pass's in runs, and the
    rest through the same function)."""
    from pushcdn_tpu.broker.staging import StageResult
    from pushcdn_tpu.broker.tasks import handlers
    real, met = handlers._stage_with_backpressure, {"link": 0, "user": 0}
    real_retry = handlers._retry_full

    def home_of(message):
        publisher = plan.HEADER.unpack_from(bytes(message.message))[0]
        return mesh.layout.group_of(mesh.layout.pub_users[publisher])

    async def counted(device, message, raw):
        if mesh.cluster.brokers.index(device.broker) != home_of(message):
            met["link"] += 1
        return await real(device, message, raw)

    async def retried(device, topics, stage_items, results, *rest):
        here = mesh.cluster.brokers.index(device.broker)
        for (message, _, _), result in zip(stage_items, results):
            if result == StageResult.FULL:
                assert home_of(message) == here
                met["user"] += 1
        return await real_retry(device, topics, stage_items, results, *rest)

    monkeypatch.setattr(handlers, "_stage_with_backpressure", counted)
    monkeypatch.setattr(handlers, "_retry_full", retried)
    return met


@pytest.mark.parametrize("brokers", [2, 4])
async def test_meshed_device_brokers_deliver_what_the_reference_owes(
        brokers, monkeypatch):
    """Rings of 16 slots (and no wider lane to spill into) under bursts of
    150 frames a publisher: the user loops and the links' receive loops
    both wait on full rings, and every stream still arrives whole, in
    order, once, at its subscribers and nobody else."""
    per_publisher = 150
    async with Mesh(brokers, _plane(16, extra_lanes=()), seed=brokers) as mesh:
        met = _count_full_rings_on_links(monkeypatch, mesh)
        planned = [mesh.frames_of(p, per_publisher)
                   for p in range(PUBLISHERS)]
        await asyncio.gather(*(mesh.publish(p, frames)
                               for p, frames in enumerate(planned)))
        log = [(p, frame.kind, frame.target)
               for p, frames in enumerate(planned) for frame, _ in frames]
        owed = reference.route(mesh.table, log)
        await wait_until(lambda: mesh.received() >= reference.total(owed),
                         timeout=90)
        await asyncio.sleep(0.3)  # a delivery too many would come now
        reports = [detector.report() for detector in mesh.detectors]
        assert reference.compare(owed, reports) == []
        assert mesh.received() == reference.total(owed)
        assert (mesh.duplicates(), mesh.foreign) == (0, 0)
        planes = [b.device_plane for b in mesh.cluster.brokers]
        assert not any(plane.disabled for plane in planes)
        assert met["link"] > 0 and met["user"] > 0, met
        # the planes count what the retry was handed: a frame once,
        # however often the ring said "full" to it again
        assert sum(plane.stage_full_frames for plane in planes) == \
            met["link"] + met["user"]
        for plane in planes:
            said = plane.describe()
            assert said["stage_full_results"] >= \
                said["stage_full_frames"] == plane.stage_full_frames > 0
        # the link counters: every broadcast crossed to every peer (each
        # topic has a subscriber there), a direct to its owner alone
        broadcasts = sum(kind == plan.BROADCAST for _, kind, _ in log)
        directs = sum(kind == plan.DIRECT for _, kind, _ in log)
        assert sum(plane.link_frames_forwarded for plane in planes) == \
            broadcasts * (brokers - 1) + directs
        for plane in planes:
            assert 0 < plane.link_frames_staged <= plane.frames_staged
            said = plane.describe()
            assert said["link_frames_staged"] == plane.link_frames_staged
            assert said["link_frames_forwarded"] == \
                plane.link_frames_forwarded
            assert said["device_memory_peak_bytes"] >= 0


@pytest.mark.parametrize("ring_slots", [64, 16])
async def test_the_native_pass_on_a_linked_broker_forwards_what_it_staged(
        ring_slots):
    """The user loop's native pass on brokers with a peer link: it stages
    the broadcasts of a batch and stops at a direct whose recipient lives
    behind the other broker (the rest of the batch is scanned), or, with
    16 slots, holds frames back for the retry; every broadcast it staged
    still reaches the peer, once, in its publisher's order, and every
    direct its owner's broker."""
    async with Mesh(2, _plane(ring_slots, extra_lanes=(),
                              bypass_max_items=0), seed=45) as mesh:
        planes = [b.device_plane for b in mesh.cluster.brokers]
        log = []
        for _ in range(3):
            planned = [mesh.frames_of(p, 32) for p in range(PUBLISHERS)]
            await asyncio.gather(*(mesh.publish(p, frames)
                                   for p, frames in enumerate(planned)))
            log += [(p, frame.kind, frame.target)
                    for p, frames in enumerate(planned)
                    for frame, _ in frames]
        owed = reference.route(mesh.table, log)
        await wait_until(lambda: mesh.received() >= reference.total(owed),
                         timeout=60)
        await asyncio.sleep(0.3)  # a delivery too many would come now
        assert reference.compare(
            owed, [d.report() for d in mesh.detectors]) == []
        assert (mesh.duplicates(), mesh.foreign) == (0, 0)
        # a probe is a direct to its own publisher: it crosses no link
        crossing = sum(kind in (plan.BROADCAST, plan.DIRECT)
                       for _, kind, _ in log)
        assert sum(p.link_frames_forwarded for p in planes) == crossing
        said = [p.describe() for p in planes]
        assert sum(d["ingress_native_frames"] for d in said) > 0
        assert sum(d["ingress_native_stops"] for d in said) > 0
        for d in said:
            assert d["ingress_native_frames"] <= d["frames_staged"]
            assert d["ingress_native_restaged"] <= d["stage_full_frames"]
        if ring_slots == 16:
            assert sum(d["ingress_native_restaged"] for d in said) > 0


async def test_lone_frames_over_a_link_take_the_idle_bypass_to_users_only():
    """One frame at a time: every batch on every link is one frame on an
    idle plane, which the bypass host-routes; a peer's frame must then
    reach this broker's users and never be forwarded again."""
    async with Mesh(2, _plane(64), seed=7) as mesh:
        planned = mesh.frames_of(0, 24)
        for item in planned:
            await mesh.publish(0, [item])
            await asyncio.sleep(0.01)
        owed = reference.route(
            mesh.table, [(0, f.kind, f.target) for f, _ in planned])
        await wait_until(lambda: mesh.received() >= reference.total(owed))
        await asyncio.sleep(0.2)
        assert reference.compare(
            owed, [d.report() for d in mesh.detectors]) == []
        assert (mesh.duplicates(), mesh.foreign) == (0, 0)
        there = mesh.cluster.brokers[1].device_plane
        assert (there.frames_staged, there.link_frames_staged,
                there.link_frames_forwarded) == (0, 0, 0)


async def test_a_frame_published_before_interest_has_crossed_stays_local():
    """Why the benchmark's launcher waits for interest and not for
    connections: a subscription made after the handshake crosses with the
    next partial sync, and what is published before that is delivered
    behind the publisher's own broker alone. The reference, which knows
    nothing of placement, owes those frames: they would be missing."""
    from pushcdn_tpu.broker.tasks.sync import partial_topic_sync
    late = TOPICS  # a topic nobody holds at the start
    async with Mesh(2, _plane(64), seed=9) as mesh:
        here, there = mesh.cluster.brokers
        local, remote = mesh.layout.group_users(0)[1], \
            mesh.layout.group_users(1)[0]
        for user in (local, remote):
            await mesh.clients[user].subscribe([late])
            mesh.table[user].add(late)
        await wait_until(lambda: all(
            late in b.connections.user_topics.values()
            for b in (here, there)))
        publisher = mesh.clients[0]
        pool = plan.make_pool(9)

        async def burst(first):
            await asyncio.gather(*(publisher.send_broadcast_message(
                [late], plan.build_payload(
                    pool, 0, plan.Frame(plan.BROADCAST, late, 100), seq, 0))
                for seq in range(first, first + 8)))

        await burst(0)
        await wait_until(
            lambda: (0, late) in mesh.detectors[local].streams
            and mesh.detectors[local].streams[0, late].unique == 8)
        await asyncio.sleep(0.3)
        assert (0, late) not in mesh.detectors[remote].streams
        await partial_topic_sync(there)
        await wait_until(lambda: late in here.connections.broker_topics
                         .get_values_of_key(str(there.identity)))
        await burst(8)
        await wait_until(
            lambda: (0, late) in mesh.detectors[remote].streams
            and mesh.detectors[remote].streams[0, late].unique == 8)
        owed = reference.route(mesh.table, [(0, plan.BROADCAST, late)] * 16)
        reports = [d.report() for d in mesh.detectors]
        assert reports[local]["0.%d" % late] == [16, 16, 0, 0, 0]
        # the second eight alone, never the first: not late, lost
        assert reports[remote]["0.%d" % late] == [8, 16, 8, 0, 0]
        problems = reference.compare(owed, reports)
        assert len(problems) == 1 and f"user {remote} " in problems[0]


@pytest.mark.parametrize("brokers", [1, 2])
async def test_traced_links_spans_conserve_the_planes_link_counters(
        brokers, tmp_path):
    """Under a profiler session: ``links.scan`` and ``links.stage`` from
    the peers' receive loops, ``links.forward`` from the user loops, flat
    with every other span on the loop's thread; Σ ``staged`` of
    ``links.stage`` = Δ``link_frames_staged`` and Σ ``forwards`` of
    ``links.forward`` = Δ``link_frames_forwarded`` over the brokers of
    the process; and not one ``links.*`` span on a broker with no peer."""
    import jax

    from pushcdn_tpu.parallel import spans
    from tests.test_plane_spans import INGRESS, PLANE, _program_spans
    spans.bind()
    async with Mesh(brokers, _plane(64, bypass_max_items=0),
                    seed=20 + brokers) as mesh:
        planes = [b.device_plane for b in mesh.cluster.brokers]

        def counted():
            return [sum(getattr(p, name) for p in planes) for name in (
                "link_frames_staged", "link_frames_forwarded",
                "frames_staged")]

        async def rounds(count):
            sent, first = [], mesh.received()
            for _ in range(count):
                for p in range(PUBLISHERS):
                    planned = mesh.frames_of(p, 16)
                    await mesh.publish(p, planned)
                    sent += [(p, f.kind, f.target) for f, _ in planned]
            owed = reference.total(reference.route(mesh.table, sent))
            await wait_until(lambda: mesh.received() - first >= owed,
                             timeout=60)

        await rounds(1)  # no session: the same path records nothing
        before = counted()
        native = sum(p.ingress_native_frames for p in planes)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            await rounds(2)
        finally:
            jax.profiler.stop_trace()
        link_staged, forwarded, staged = (
            b - a for a, b in zip(before, counted()))
        # the user loops' native pass took frames meanwhile
        assert sum(p.ingress_native_frames for p in planes) > native
    threads, _ = _program_spans(str(tmp_path))
    events = [e for evs in threads.values() for e in evs]
    names = {e[0] for e in events}

    def total(name, stat):
        return sum(e[3][stat] for e in events if e[0] == name)

    if brokers == 1:
        assert names == set(PLANE + INGRESS)
        assert (link_staged, forwarded) == (0, 0)
        return
    assert names == set(PLANE + INGRESS + LINKS)
    for evs in threads.values():  # flat, the new spans among the old
        evs.sort(key=lambda e: e[1])
        for (a, _s, a_end, _), (b, b_start, _e, _) in zip(evs, evs[1:]):
            assert a_end <= b_start, (a, b)
    loop_thread = {i for i, evs in threads.items()
                   for e in evs if e[0] == "plane.take"}
    for name in LINKS:
        assert {i for i, evs in threads.items()
                for e in evs if e[0] == name} == loop_thread, name
    assert 0 < link_staged < staged
    assert total("links.stage", "staged") == link_staged
    assert total("links.forward", "forwards") == forwarded > 0
    assert total("links.scan", "frames") >= \
        total("links.stage", "frames") >= link_staged
    # every frame a user loop staged or routed went through the pass
    assert total("links.forward", "frames") == \
        total("ingress.stage", "frames")
    assert total("ingress.stage", "staged") + link_staged == staged
