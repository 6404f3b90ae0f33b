"""The user table follows who connects (ISSUE 27): a growth differential.

One seeded script — 2,200 users in three waves over the Memory transport,
bursts of broadcasts and directs between the waves and one step held in
flight while the second wave connects — runs through a broker with no
plane (the scalar host router) and through the device plane, dense and
ragged. Every user's ``(publisher, stream)`` sequences must be the same
both ways, and their counts what ``benchmark.reference.route`` owes for
the membership at each frame's publish time.
"""

import asyncio
import os
import random
import sys
import tempfile
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import reference  # noqa: E402
from benchmark.loadgen import plan  # noqa: E402
from pushcdn_tpu.proto.message import Broadcast, Direct  # noqa: E402
from tests.test_integration import wait_until  # noqa: E402

WAVES = (900, 700, 600)   # 2,200 users: past 1,024, then past 2,048
PUBLISHERS = (0, 1, 2, 3)
QUIT = 17                 # leaves while the held step is in flight
SEED = 2701


def _topics(u: int) -> set:
    return {0} | ({1} if u % 10 == 0 else set()) | ({2} if u % 7 == 3 else set())


def _key(u: int) -> bytes:
    return b"grow-user-%05d" % u


class _Script:
    """The frames of each phase, drawn once from the seed: per publisher a
    list of ``(kind, target)``; directs go to users connected (and not
    about to leave) at that phase, plus the ones a phase names."""

    def __init__(self):
        rng = random.Random(SEED)

        def burst(n, live, must=()):
            frames = [(plan.DIRECT, t) for t in must]
            while len(frames) < n:
                r = rng.random()
                if r < 0.3:
                    frames.append((plan.BROADCAST, 0))
                elif r < 0.5:
                    frames.append((plan.BROADCAST, 1))
                elif r < 0.7:
                    frames.append((plan.BROADCAST, 2))
                else:
                    frames.append((plan.DIRECT, rng.choice(live)))
            rng.shuffle(frames)
            return frames

        w1 = [u for u in range(WAVES[0]) if u != QUIT]
        w2 = w1 + list(range(WAVES[0], WAVES[0] + WAVES[1]))
        w3 = w2 + list(range(WAVES[0] + WAVES[1], sum(WAVES)))
        self.between_1 = {p: burst(6, w1) for p in PUBLISHERS}
        # one publisher, one write: the held step carries all of it
        self.held = {0: burst(12, w1, must=[5])}
        # a direct to a slot above 1,023 (user 1,500 sits in slot 1,500)
        self.between_2 = {p: burst(6, w2, must=[1500] if p == 1 else ())
                          for p in PUBLISHERS}
        # to the user that took the recycled slot, and above 2,047
        self.between_3 = {p: burst(6, w3, must=[1600, 2150] if p == 2 else ())
                          for p in PUBLISHERS}


async def _run(device_plane):
    """Run the script through one broker; returns per user
    ``{(publisher, stream): [seq, ...]}``, what the reference owes per
    user, and the plane (or None)."""
    from pushcdn_tpu.broker.broker import Broker, BrokerConfig
    from pushcdn_tpu.broker.tasks.handlers import user_receive_loop
    from pushcdn_tpu.proto.crypto.signature import DEFAULT_SCHEME
    from pushcdn_tpu.proto.def_ import testing_run_def
    from pushcdn_tpu.proto.topic import TopicSpace
    from pushcdn_tpu.proto.transport.memory import gen_testing_connection_pair
    from pushcdn_tpu.proto.util import AbortOnDropHandle

    tag = f"grow-{id(device_plane)}-{random.getrandbits(32)}"
    db = os.path.join(tempfile.mkdtemp(prefix="pushcdn-grow-"), "d.sqlite")
    broker = await Broker.new(BrokerConfig(
        run_def=testing_run_def(topics=TopicSpace.range(3)),
        keypair=DEFAULT_SCHEME.generate_keypair(seed=SEED),
        discovery_endpoint=db,
        public_advertise_endpoint=f"{tag}-pub",
        public_bind_endpoint=f"{tag}-pub",
        private_advertise_endpoint=f"{tag}-priv",
        private_bind_endpoint=f"{tag}-priv",
        heartbeat_interval_s=3600, sync_interval_s=3600,
        whitelist_interval_s=3600, device_plane=device_plane))
    await broker.start()
    plane = broker.device_plane
    script = _Script()
    total = sum(WAVES)
    remotes, drains = {}, {}
    got = [{} for _ in range(total)]
    counts = [0] * total
    owed = [{} for _ in range(total)]          # by the plain reference
    seqs = {}                                   # next of each stream
    live = set()

    async def drain(u):
        while True:
            raw = await remotes[u].recv_raw()
            p, stream, seq, _ = bytes(raw.data).rsplit(b"#", 1)[1].split(b"|", 3)
            raw.release()
            got[u].setdefault((int(p), int(stream)), []).append(int(seq))
            counts[u] += 1

    async def connect(users):
        for u in users:
            local, remote = await gen_testing_connection_pair(broker.limiter)
            task = asyncio.create_task(
                user_receive_loop(broker, _key(u), local))
            broker.connections.add_user(_key(u), local, sorted(_topics(u)),
                                        AbortOnDropHandle(task))
            remotes[u] = remote
            drains[u] = asyncio.create_task(drain(u))
            live.add(u)

    async def publish(frames_by_pub):
        """Each publisher's burst as one write (nothing here yields
        between the sends, so a connection's writer task finds them all
        queued); the reference is owed the same frames against the
        membership of this moment."""
        table = [_topics(u) if u in live else set() for u in range(total)]
        log = []
        for p, frames in frames_by_pub.items():
            for kind, target in frames:
                stream = target if kind == plan.BROADCAST \
                    else plan.STREAM_DIRECT
                # as the benchmark's plan counts: per (publisher, topic)
                # for broadcasts, per (publisher, recipient) for directs
                seq = seqs[p, stream, target] = \
                    seqs.get((p, stream, target), -1) + 1
                body = b"#%d|%d|%d|" % (p, stream, seq) + b"." * 40
                message = Broadcast(topics=[target], message=body) \
                    if kind == plan.BROADCAST \
                    else Direct(recipient=_key(target), message=body)
                await remotes[p].send_message(message, flush=False)
                log.append((p, kind, target))
        for u, row in enumerate(reference.route(table, log)):
            for key, n in row.items():
                owed[u][key] = owed[u].get(key, 0) + n

    def settled():
        return all(counts[u] == sum(owed[u].values()) for u in live)

    checks = {}
    try:
        # wave 1, then traffic on a table that has not grown
        await connect(range(WAVES[0]))
        await publish(script.between_1)
        await wait_until(settled, timeout=60)

        # one step held in flight while a user leaves and wave 2 connects
        entered, release = threading.Event(), threading.Event()
        if plane is not None:
            real = plane._run_step

            def held(*args, **kwargs):
                if not entered.is_set():
                    entered.set()
                    assert release.wait(60), "the held step was never released"
                return real(*args, **kwargs)
            plane._run_step = held
            staged0 = plane.frames_staged
        await publish(script.held)
        if plane is not None:
            await wait_until(entered.is_set, timeout=60)
            # the whole burst is in the held step, none waits in a ring
            assert plane.frames_staged - staged0 == len(script.held[0])
            assert all(r.free_slots == r.slots for r in plane.rings)
            assert plane._step_inflight
        else:
            await wait_until(settled, timeout=60)
        live.discard(QUIT)
        drains.pop(QUIT).cancel()
        broker.connections.remove_user(_key(QUIT), reason="test: left")
        remotes[QUIT].close()
        await connect(range(WAVES[0], WAVES[0] + WAVES[1]))
        if plane is not None:
            checks["held"] = dict(
                grows=plane.table_grows, slots=plane.user_slots,
                inflight=plane._step_inflight,
                quit_slot_owner=plane.slots.key_of(QUIT),
                first_new_slot=plane.slots.slot_of(_key(1024)))
            release.set()
        await wait_until(settled, timeout=60)

        # between the waves, on the grown table
        if plane is not None:
            checks["slot_1500"] = plane.slots.slot_of(_key(1500))
        await publish(script.between_2)
        await wait_until(settled, timeout=60)

        # wave 3: its first user takes the slot the leaver gave back
        await connect(range(WAVES[0] + WAVES[1], total))
        if plane is not None:
            checks["slot_1600"] = plane.slots.slot_of(_key(1600))
            checks["slot_2150"] = plane.slots.slot_of(_key(2150))
        await publish(script.between_3)
        await wait_until(settled, timeout=60)
        await asyncio.sleep(0.2)   # anything misdelivered would land now
        assert settled()
        assert broker.connections.num_users == total - 1
    finally:
        for t in drains.values():
            t.cancel()
        for r in remotes.values():
            r.close()
        await broker.stop()
    return got, owed, plane, checks


_by_host = {}


async def _host_router():
    """The scalar host router's answer, worked out once per process."""
    if not _by_host:
        got, owed, plane, _ = await _run(None)
        assert plane is None
        _by_host.update(got=got, owed=owed)
    return _by_host["got"], _by_host["owed"]


@pytest.mark.parametrize("impl", ["dense", "ragged"])
async def test_growth_differential_against_the_host_router(impl):
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig

    by_host, owed_host = await _host_router()
    by_device, owed, plane, checks = await _run(DevicePlaneConfig(
        delivery_impl=impl, batch_window_s=0.002, bypass_max_items=0))
    assert owed == owed_host
    # every user, every (publisher, stream): the same sequence both ways
    for u, (dev, host) in enumerate(zip(by_device, by_host)):
        if u != QUIT:
            assert dev == host, (u, dev, host)
    # and the multiset the plain reference owes: as many of each stream
    # as were published while the user was connected, which is one run of
    # consecutive sequence numbers, in order
    for u, row in enumerate(owed):
        if u != QUIT:
            assert {k: len(v) for k, v in by_device[u].items()} == row, u
            for v in by_device[u].values():
                assert v == list(range(v[0], v[0] + len(v))), (u, v)
    assert sum(len(s) for user in by_device for s in user.values()) > 20_000

    # the table grew twice, under traffic, and nobody fell off the device
    assert plane.table_grows == 2 and plane.user_slots == 4096
    # a step runs at the power of two that holds the high-water mark,
    # here the capacity; the pump loaded its programs when the table grew
    assert plane._step_users() == 4096 == plane._loaded_users
    assert plane.describe()["user_high_water"] == sum(WAVES) - 1
    assert not plane._unmirrored and not plane.disabled
    assert plane.delivery_impl == impl
    if impl == "ragged":
        assert plane.ragged_steps >= 4 and plane.ragged_fallbacks == 0
    # nothing was host-routed: every frame of the script was staged
    assert plane.frames_staged == 12 + 3 * 6 * len(PUBLISHERS)
    assert plane.messages_routed == sum(
        len(s) for user in by_device for s in user.values())
    held = checks["held"]
    # the step was in flight across the first growth, and the slot of the
    # user that left stayed out of circulation until it completed
    assert held["inflight"] and held["grows"] == 1 and held["slots"] == 2048
    assert held["quit_slot_owner"] is None and held["first_new_slot"] == 1024
    assert checks["slot_1500"] == 1500      # a direct above slot 1,023
    assert checks["slot_1600"] == QUIT      # the slot reused after growth
    assert checks["slot_2150"] >= 2048      # a direct above the second mark
