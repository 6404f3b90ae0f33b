"""MeshBrokerGroup integration: inter-broker traffic rides the device mesh
step (all_gather over the virtual CPU mesh) with NO host broker links —
the north-star path (BASELINE.json config 4 shape) in miniature."""

import asyncio
import contextlib
import os

import numpy as np
import pytest

from pushcdn_tpu.broker.mesh_group import MeshBrokerGroup, MeshGroupConfig
from pushcdn_tpu.parallel.mesh import make_broker_mesh
from pushcdn_tpu.proto.message import Broadcast, Direct
from pushcdn_tpu.proto.transport import Memory, Tcp
from pushcdn_tpu.testing.mesh_cluster import MeshCluster
from tests.test_device_plane import (
    _broker_fd,
    _egress_account,
    _moved,
    _never_whole,
    _receive_all,
    _record_batches,
    _shut_down_before_the_batch,
    _socket_of,
    _stall_reader,
    _watch_settling,
    _wire,
)
from tests.test_integration import wait_until



async def test_cross_shard_broadcast_over_mesh_only():
    """4 shards, no host broker links: a broadcast reaches subscribers on
    every shard purely via the device mesh all_gather."""
    cluster = await MeshCluster(num_shards=4).start(form_host_mesh=False)
    try:
        clients = []
        for shard in range(4):
            clients.append(await cluster.place_client(
                seed=100 + shard, shard=shard, topics=[0]))
        # sanity: NO host broker links exist
        for b in cluster.brokers:
            assert b.connections.num_brokers == 0

        await clients[0].send_broadcast_message([0], b"over the mesh")
        for c in clients:
            got = await asyncio.wait_for(c.receive_message(), 10)
            assert isinstance(got, Broadcast)
            assert bytes(got.message) == b"over the mesh"
        assert cluster.group.steps >= 1
        assert cluster.group.messages_routed >= 4
        for c in clients:
            c.close()
    finally:
        await cluster.stop()


async def test_cross_shard_direct_over_mesh_only():
    cluster = await MeshCluster(num_shards=4).start(form_host_mesh=False)
    try:
        alice = await cluster.place_client(seed=200, shard=0, topics=[0])
        bob = await cluster.place_client(seed=201, shard=3, topics=[0])
        for b in cluster.brokers:
            assert b.connections.num_brokers == 0

        await alice.send_direct_message(bob.public_key, b"shard 0 -> shard 3")
        got = await asyncio.wait_for(bob.receive_message(), 10)
        assert isinstance(got, Direct)
        assert bytes(got.message) == b"shard 0 -> shard 3"
        # exactly-once: nothing else arrives
        with_timeout = asyncio.create_task(bob.receive_message())
        await asyncio.sleep(0.3)
        assert not with_timeout.done()
        with_timeout.cancel()
        alice.close()
        bob.close()
    finally:
        await cluster.stop()


async def test_cross_shard_traffic_with_gathered_bytes():
    """The multi-host configuration (gather_frame_bytes=True): frame bytes
    ride the step's collectives and egress decodes from the DEVICE-gathered
    tensors. The all_to_all direct output differs per shard — regression
    for pairing shard j's delivery mask with shard 0's received bytes."""
    cluster = await MeshCluster(
        num_shards=4, gather_frame_bytes=True).start(form_host_mesh=False)
    try:
        alice = await cluster.place_client(seed=210, shard=0, topics=[0])
        bob = await cluster.place_client(seed=211, shard=3, topics=[0])
        carol = await cluster.place_client(seed=212, shard=1, topics=[0])

        await alice.send_direct_message(bob.public_key, b"gathered 0 -> 3")
        got = await asyncio.wait_for(bob.receive_message(), 10)
        assert isinstance(got, Direct)
        assert bytes(got.message) == b"gathered 0 -> 3"

        await carol.send_broadcast_message([0], b"gathered bcast")
        for c in (alice, bob, carol):
            got = await asyncio.wait_for(c.receive_message(), 10)
            assert isinstance(got, Broadcast)
            assert bytes(got.message) == b"gathered bcast"
        for c in (alice, bob, carol):
            c.close()
    finally:
        await cluster.stop()


async def test_in_group_double_connect_kick():
    """The same identity connecting at a second shard kicks the first
    session immediately (authoritative in-group claim)."""
    cluster = await MeshCluster(num_shards=2).start(form_host_mesh=False)
    try:
        c1 = await cluster.place_client(seed=300, shard=0, topics=[0])
        c2 = await cluster.place_client(seed=300, shard=1, topics=[0])
        await wait_until(
            lambda: not cluster.brokers[0].connections.has_user(c1.public_key))
        assert cluster.brokers[1].connections.has_user(c2.public_key)
        # the surviving session still receives device-routed traffic
        await c2.send_direct_message(c2.public_key, b"still routed")
        got = await asyncio.wait_for(c2.receive_message(), 10)
        assert bytes(got.message) == b"still routed"
        c1.close()
        c2.close()
    finally:
        await cluster.stop()


async def test_mesh_group_host_fallback_on_step_failure():
    """If the device step blows up, staged frames re-route over the host
    links and the group disables itself (fail-open)."""
    cluster = await MeshCluster(num_shards=2).start(form_host_mesh=True)
    try:
        alice = await cluster.place_client(seed=400, shard=0, topics=[1])
        bob = await cluster.place_client(seed=401, shard=1, topics=[1])
        # host links exist as backup
        assert all(b.connections.num_brokers == 1 for b in cluster.brokers)

        # sabotage the device step
        def boom(*_a, **_k):
            raise RuntimeError("injected step failure")
        cluster.group.step_fn = boom

        await alice.send_broadcast_message([1], b"survives the failure")
        got = await asyncio.wait_for(bob.receive_message(), 10)
        assert bytes(got.message) == b"survives the failure"
        assert cluster.group.disabled
        # subsequent traffic flows purely on the host plane
        await alice.send_broadcast_message([1], b"host plane now")
        got2 = await asyncio.wait_for(bob.receive_message(), 10)
        assert bytes(got2.message) == b"host plane now"
        alice.close()
        bob.close()
    finally:
        await cluster.stop()


async def test_staged_broadcast_still_forwards_to_out_of_group_broker():
    """Mixed deployment: a broadcast staged on the mesh must STILL be
    forwarded over host links to interested brokers OUTSIDE the group."""
    from pushcdn_tpu.proto.transport.memory import gen_testing_connection_pair

    cluster = await MeshCluster(num_shards=2).start(form_host_mesh=False)
    try:
        alice = await cluster.place_client(seed=500, shard=0, topics=[0])
        # attach an out-of-group broker to shard 0 over a host link, with
        # interest in topic 0 (harness-style injection)
        ext_ident = "external-pub:1/external-priv:1"
        local, remote = await gen_testing_connection_pair()
        cluster.brokers[0].connections.add_broker(ext_ident, local)
        cluster.brokers[0].connections.subscribe_broker_to(ext_ident, [0])

        await alice.send_broadcast_message([0], b"reach outside too")
        # the device plane delivers alice's copy...
        got = await asyncio.wait_for(alice.receive_message(), 10)
        assert bytes(got.message) == b"reach outside too"
        # ...AND the external broker got a host-forwarded copy
        raw = await asyncio.wait_for(remote.recv_raw(), 10)
        from pushcdn_tpu.proto.message import deserialize
        ext_msg = deserialize(raw.data)
        assert isinstance(ext_msg, Broadcast)
        assert bytes(ext_msg.message) == b"reach outside too"
        raw.release()
        remote.close()
        alice.close()
    finally:
        await cluster.stop()


async def test_overflow_traffic_triggers_host_links_in_mesh_only_mode():
    """Mesh-only deployment (no host links formed up-front): traffic the
    device plane can't carry — here an oversized frame — must flag
    overflow, kick the heartbeat into dialing host links, and then flow
    cross-shard over those links instead of being silently lost."""
    cluster = await MeshCluster(num_shards=2).start(form_host_mesh=False)
    try:
        alice = await cluster.place_client(seed=600, shard=0, topics=[1])
        bob = await cluster.place_client(seed=601, shard=1, topics=[1])
        for b in cluster.brokers:
            assert b.connections.num_brokers == 0

        big = b"x" * 4096  # frame_bytes=1024 ⇒ ineligible for the mesh step
        await alice.send_broadcast_message([1], big)
        await wait_until(lambda: cluster.group.overflow_seen)
        # the kicked heartbeat forms host links promptly
        await wait_until(
            lambda: all(b.connections.num_brokers >= 1
                        for b in cluster.brokers))
        # with links up, oversized traffic crosses shards on the host plane
        await alice.send_broadcast_message([1], big + b"2")
        got = await asyncio.wait_for(bob.receive_message(), 10)
        assert bytes(got.message) == big + b"2"
        # and eligible traffic still rides the device mesh, exactly once
        await alice.send_broadcast_message([1], b"small still on mesh")
        got2 = await asyncio.wait_for(bob.receive_message(), 10)
        assert bytes(got2.message) == b"small still on mesh"
        pending = asyncio.create_task(bob.receive_message())
        await asyncio.sleep(0.3)
        assert not pending.done()  # no duplicate via host + mesh
        pending.cancel()
        alice.close()
        bob.close()
    finally:
        await cluster.stop()


async def test_size_bucketed_lanes_carry_large_frames_on_mesh():
    """Hard-part #1: with an extra 16 KB lane configured, frames too big
    for the base 1 KB lane still cross shards on the device mesh (no host
    links exist to fall back to), while small frames ride the base lane —
    each delivered exactly once."""
    cluster = await MeshCluster(
        num_shards=2, extra_lanes=((16384, 8, 4),),
    ).start(form_host_mesh=False)
    try:
        alice = await cluster.place_client(seed=700, shard=0, topics=[1])
        bob = await cluster.place_client(seed=701, shard=1, topics=[1])
        for b in cluster.brokers:
            assert b.connections.num_brokers == 0  # mesh-only

        big = b"L" * 8000   # > base lane (1 KB), fits the 16 KB lane
        await alice.send_broadcast_message([1], big)
        got = await asyncio.wait_for(bob.receive_message(), 10)
        assert bytes(got.message) == big
        assert not cluster.group.overflow_seen  # the lane carried it

        # direct frames use the lane buckets the same way
        await alice.send_direct_message(bob.public_key, b"D" * 4000)
        got2 = await asyncio.wait_for(bob.receive_message(), 10)
        assert bytes(got2.message) == b"D" * 4000

        await alice.send_broadcast_message([1], b"small lane")
        got3 = await asyncio.wait_for(bob.receive_message(), 10)
        assert bytes(got3.message) == b"small lane"

        pending = asyncio.create_task(bob.receive_message())
        await asyncio.sleep(0.3)
        assert not pending.done()  # exactly-once across lanes
        pending.cancel()
        alice.close()
        bob.close()
    finally:
        await cluster.stop()


async def test_shard_departure_survivors_keep_routing():
    """Hard-part #3 at the group level: one shard of a 3-shard mesh-only
    group stops; the static device mesh stays up, the stopped shard is
    masked dead, and the survivors keep exchanging traffic over the mesh
    with no host links and no group disable."""
    cluster = await MeshCluster(num_shards=3).start(form_host_mesh=False)
    try:
        alice = await cluster.place_client(seed=800, shard=0, topics=[0])
        bob = await cluster.place_client(seed=801, shard=1, topics=[0])
        carol = await cluster.place_client(seed=802, shard=2, topics=[0])

        await alice.send_broadcast_message([0], b"all three")
        for c in (alice, bob, carol):
            got = await asyncio.wait_for(c.receive_message(), 10)
            assert bytes(got.message) == b"all three"

        # shard 2 departs (its client goes with it)
        carol.close()
        await cluster.brokers[2].stop()
        assert not cluster.group._liveness[2]
        assert not cluster.group.disabled

        await alice.send_broadcast_message([0], b"survivors")
        for c in (alice, bob):
            got = await asyncio.wait_for(c.receive_message(), 10)
            assert bytes(got.message) == b"survivors"
        await alice.send_direct_message(bob.public_key, b"still one hop")
        got = await asyncio.wait_for(bob.receive_message(), 10)
        assert bytes(got.message) == b"still one hop"
        for b in cluster.brokers[:2]:
            assert b.connections.num_brokers == 0  # still mesh-only
        alice.close()
        bob.close()
    finally:
        await cluster.stop()


async def test_dead_shard_sweep_releases_slots():
    """on_shard_stopped must release every slot the dead shard still owned
    (a crashed broker fires no per-user removals): directs to its users
    then overflow to the host path instead of being staged at a ghost, and
    the slot table doesn't leak."""
    mesh = make_broker_mesh(2)
    group = MeshBrokerGroup(mesh, MeshGroupConfig(
        num_user_slots=8, ring_slots=4, frame_bytes=512, extra_lanes=()))
    group._liveness[:] = True
    group.claim_user(0, b"alice-key", [0])
    group.claim_user(1, b"bob-key", [0])
    assert len(group.slots) == 2

    # shard 1 "crashes": declared dead without per-user removals
    await group.on_shard_stopped(1)
    assert group.slots.slot_of(b"bob-key") is None  # mapping swept
    assert group.slots.slot_of(b"alice-key") is not None  # survivor intact
    assert not group._liveness[1]
    # swept slot is quarantined until the next step, then reusable
    assert len(group._quarantine) == 1


async def test_mid_session_subscribe_over_mesh():
    """A subscription added AFTER connect must reach the device mirrors
    (update_mask) and start delivering cross-shard broadcasts; an
    unsubscribe stops them."""
    cluster = await MeshCluster(num_shards=2).start(form_host_mesh=False)
    try:
        pub = await cluster.place_client(seed=950, shard=0, topics=[0])
        sub = await cluster.place_client(seed=951, shard=1, topics=[])

        # not subscribed yet: only the publisher (topic 0) receives
        await pub.send_broadcast_message([1], b"before subscribe")
        pending = asyncio.create_task(sub.receive_message())
        await asyncio.sleep(0.3)
        assert not pending.done(), "unsubscribed client received a broadcast"

        await sub.subscribe([1])
        await wait_until(lambda: bool(
            cluster.group._masks[
                cluster.group.slots.slot_of(sub.public_key)].any()))
        await pub.send_broadcast_message([1], b"after subscribe")
        got = await asyncio.wait_for(pending, 10)
        assert bytes(got.message) == b"after subscribe"

        await sub.unsubscribe([1])
        await wait_until(lambda: not
            cluster.group._masks[
                cluster.group.slots.slot_of(sub.public_key)].any())
        await pub.send_broadcast_message([1], b"after unsubscribe")
        late = asyncio.create_task(sub.receive_message())
        await asyncio.sleep(0.3)
        assert not late.done(), "unsubscribed client still receives"
        late.cancel()
        pub.close()
        sub.close()
    finally:
        await cluster.stop()


async def test_mesh_chaos_shard_death_under_load():
    """Device-mesh chaos tier: a shard dies MID-STREAM while a publisher
    keeps sending; survivors receive every message published after the
    death settles, and the group neither disables nor leaks the dead
    shard's slots."""
    cluster = await MeshCluster(num_shards=4, ring_slots=32).start(
        form_host_mesh=False)
    try:
        pub = await cluster.place_client(seed=980, shard=0, topics=[0])
        doomed = await cluster.place_client(seed=981, shard=2, topics=[0])
        survivors = [pub,
                     await cluster.place_client(seed=982, shard=1,
                                                topics=[0]),
                     await cluster.place_client(seed=983, shard=3,
                                                topics=[0])]
        received = [[] for _ in survivors]

        async def drain(idx):
            while True:
                for m in await survivors[idx].receive_messages():
                    received[idx].append(bytes(m.message))

        drains = [asyncio.create_task(drain(i))
                  for i in range(len(survivors))]
        stop_stream = asyncio.Event()
        sent = []

        async def stream():
            seq = 0
            while not stop_stream.is_set():
                payload = b"chaos-%06d" % seq
                await pub.send_broadcast_message([0], payload)
                sent.append(payload)
                seq += 1
                await asyncio.sleep(0.01)

        try:
            streamer = asyncio.create_task(stream())
            await asyncio.sleep(0.3)             # traffic flowing
            doomed.close()                       # client gone...
            await cluster.brokers[2].stop()      # ...and its shard dies
            await asyncio.sleep(0.5)             # group sweeps + settles
            # every message sent AFTER the death must reach all survivors
            post_death_from = len(sent)
            await asyncio.sleep(1.0)
            stop_stream.set()
            await streamer
            post = sent[post_death_from:]
            assert post, "stream never progressed after the shard death"

            def converged():
                for t in drains:  # surface a dead drain's real exception
                    if t.done():
                        t.result()
                return all(set(post) <= set(r) for r in received)

            await wait_until(converged, timeout=20)
        finally:
            stop_stream.set()
            for t in drains:
                t.cancel()
        assert not cluster.group.disabled
        # the doomed user's slot is gone after the (graceful) teardown;
        # the CRASH-path sweep is pinned separately by
        # test_dead_shard_sweep_releases_slots
        assert cluster.group.slots.slot_of(doomed.public_key) is None
        for c in survivors:
            c.close()
    finally:
        await cluster.stop()


async def test_mesh_tick_is_one_collective():
    """ISSUE 8: the group's default (fused) tick traces exactly ONE
    collective — the counted one-collective-per-tick invariant, observed
    at the running group (router.trace_collectives delta captured around
    the compiled step)."""
    cluster = await MeshCluster(num_shards=4).start(form_host_mesh=False)
    try:
        assert cluster.group.config.fused_collective
        a = await cluster.place_client(seed=900, shard=0, topics=[0])
        b = await cluster.place_client(seed=901, shard=2, topics=[0])
        await a.send_broadcast_message([0], b"tick")
        got = await asyncio.wait_for(b.receive_message(), 10)
        assert bytes(got.message) == b"tick"
        assert cluster.group.collectives_last_trace == 1, \
            cluster.group.collectives_last_trace
        a.close()
        b.close()
    finally:
        await cluster.stop()


# ---------------------------------------------------------------------------
# a tick's upload (ISSUE 36): every lane's metadata in one buffer, one
# ``device_put``; frame bytes only where ``gather_frame_bytes`` has the step
# gather them.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("gather", [False, True],
                         ids=["host_bytes", "gathered_bytes"])
async def test_a_tick_uploads_what_its_step_reads_in_one_transfer(
        gather, monkeypatch):
    """At ``MeshGroupConfig``'s default shapes a steady tick makes ONE
    ``device_put``, under 128 KiB, and hands the device nothing of a
    lane's slots x width; a tick after a membership change adds the
    state's. With ``gather_frame_bytes`` a busy shard's blocks cross too,
    and every client receives the same frames either way."""
    import jax
    d = MeshGroupConfig()
    handed = []  # (shape, nbytes) of every array given to device_put
    real_put = jax.device_put

    def recording_put(x, *args, **kwargs):
        handed.append((np.shape(x), np.asarray(x).nbytes))
        return real_put(x, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", recording_put)
    cluster = await MeshCluster(
        num_shards=4, num_user_slots=d.num_user_slots,
        ring_slots=d.ring_slots, frame_bytes=d.frame_bytes,
        extra_lanes=d.extra_lanes,
        direct_bucket_slots=d.direct_bucket_slots,
        gather_frame_bytes=gather).start()
    clients = []
    try:
        group = cluster.group
        for shard in range(4):
            clients.append(await cluster.place_client(
                seed=3600 + shard, shard=shard, topics=[0]))
        expected = [[] for _ in clients]

        async def round_(tag: bytes):
            """Base and wide broadcasts from shard 0, a direct from every
            shard to the next; every client reads what it is owed."""
            for i in range(12):
                payload = tag + b" small %d" % i
                await clients[0].send_broadcast_message([0], payload)
                for got in expected:
                    got.append(payload)
            wide = tag + b" wide " + b"w" * 9000
            await clients[0].send_broadcast_message([0], wide)
            for got in expected:
                got.append(wide)
            for shard, c in enumerate(clients):
                nxt = (shard + 1) % 4
                payload = tag + b" direct from %d" % shard
                await c.send_direct_message(clients[nxt].public_key, payload)
                expected[nxt].append(payload)

        received = [[] for _ in clients]

        async def read_all():
            async with asyncio.timeout(30):
                for c, got, want in zip(clients, received, expected):
                    while len(got) < len(want):
                        got.extend(bytes(m.message)
                                   for m in await c.receive_messages())

        # the first ticks carry the four claims: the state crosses again
        puts, steps = group.h2d_puts, group.steps
        await round_(b"first")
        await read_all()
        assert group.h2d_puts - puts > group.steps - steps
        # steady: the membership is what the last tick saw
        del handed[:]
        puts, nbytes, steps = group.h2d_puts, group.h2d_bytes, group.steps
        await round_(b"steady")
        await read_all()
        ticks = group.steps - steps
        assert ticks >= 1
        words = [n for shape, n in handed if len(shape) == 2]
        assert len(words) == ticks and len(set(words)) == 1
        assert words[0] < 131072
        assert (group.h2d_puts - puts, group.h2d_bytes - nbytes) == (
            len(handed), sum(n for _shape, n in handed))
        if gather:
            # a busy shard's block a busy lane, whole
            blocks = [shape for shape, _n in handed if len(shape) > 2]
            assert blocks and {s[-1] for s in blocks} <= {
                d.frame_bytes, d.extra_lanes[0][0]}
            assert (1, d.ring_slots, d.frame_bytes) in blocks
        else:
            assert len(handed) == ticks
            assert all(n < d.ring_slots * d.frame_bytes
                       for _shape, n in handed)
        assert [b.device_plane.describe()["h2d_puts"]
                for b in cluster.brokers] == [group.h2d_puts] * 4
        assert [sorted(got) for got in received] == \
            [sorted(want) for want in expected]
        assert not group.disabled and not group.overflow_seen
    finally:
        for c in clients:
            c.close()
        await cluster.stop()


# ---------------------------------------------------------------------------
# the group's native batch (ISSUE 34): the sends of a tick whose take found a
# live shard's base ring or a base direct bucket full leave in one
# ``native.send_batch`` call a lane, for the links that are idle plain sockets;
# every other tick, and every other link, goes one by one (``DevicePlane``'s
# twin: tests/test_device_plane.py, "the native batch").
# ---------------------------------------------------------------------------

_RING, _BUCKET, _WIDE = 16, 8, 2


@contextlib.asynccontextmanager
async def _served_group(per_shard: int = 2, user_protocol=Tcp,
                        ring_slots: int = _RING):
    """A four-shard group with small lanes and ``per_shard`` users a shard
    on topic 0, over real TCP user links (what
    ``benchmark/launchers/mesh_inprocess.py`` wires) or ``user_protocol``'s:
    ``(cluster, clients)``, shard ``s``'s users from
    ``clients[s * per_shard]`` on."""
    cluster = await MeshCluster(
        num_shards=4, ring_slots=ring_slots, direct_bucket_slots=_BUCKET,
        extra_lanes=((4096, _WIDE, _WIDE),),
        user_protocol=user_protocol).start()
    clients = []
    try:
        for shard in range(4):
            for i in range(per_shard):
                clients.append(await cluster.place_client(
                    seed=3400 + 10 * shard + i, shard=shard, topics=[0]))
        yield cluster, clients
    finally:
        for c in clients:
            c.close()
        await cluster.stop()


@pytest.mark.parametrize("lane, frames, pressured", [
    ("ring", _RING, True), ("ring", _RING - 1, False),
    ("bucket", _BUCKET, True), ("bucket", _BUCKET - 1, False),
    ("wide_ring", _WIDE, False)],
    ids=["ring_full", "ring_one_short", "bucket_full", "bucket_one_short",
         "wide_ring_full"])
async def test_only_a_back_pressured_tick_is_sent_by_the_native_batch(
        lane, frames, pressured, monkeypatch):
    """One shard's base ring full at the take batches every idle link of
    every shard in one call (the tick is lockstep: the observation is the
    group's, and a lane's egress is one job over all shards); so
    does one full base direct bucket alone; one frame short of either, or
    a full wide lane, batches nothing. ``egress_batched`` is what the
    recorded calls sent, and what ``describe()`` says."""
    payloads = [(b"frame %d" % i).ljust(2000 if lane == "wide_ring" else 8)
                for i in range(frames)]
    calls = _record_batches(monkeypatch)
    async with _served_group() as (cluster, clients):
        group = cluster.group
        publisher, recipient = clients[0], clients[2]   # shards 0 and 1
        # one write, one read, one receive batch: the take finds what it
        # staged
        if lane == "bucket":
            readers = [2]
            wire = _wire(*payloads, to=recipient.public_key)
        else:
            readers = list(range(8))
            wire = _wire(*payloads)
        os.write(_socket_of(publisher), wire)
        got = await _receive_all([clients[u] for u in readers], frames)
        assert got == [payloads] * len(readers)
        assert (group.steps, group.egress_inline, group.egress_queued) == \
            (1, len(readers), 0)
        if pressured:
            # one call for the lane's job, whichever shard holds the user
            (fds, nbytes, sent), = calls
            assert sorted(fds) == sorted(
                _broker_fd(cluster.brokers[u // 2], clients[u])
                for u in readers)
            assert sent == nbytes
            assert group.egress_batched == len(readers)
        else:
            assert group.egress_batched == 0 and not calls
        assert [b.device_plane.describe()["egress_batched"]
                for b in cluster.brokers] == [group.egress_batched] * 4


@pytest.mark.parametrize("first_send", ["whole", "short"])
async def test_a_users_two_streams_of_one_tick_keep_their_order(
        first_send, monkeypatch):
    """A tick hands a user its broadcasts and its directs as two streams
    (two jobs, two calls): the link is checked again for the second after
    the first has settled. A whole first send leaves the link idle and the
    second is batched too; so does a short one (a reader that stopped
    reading) whose remainder the transport could write at once; where the
    transport holds some of it the second queues behind, and the link is
    not batched again while it does. Either way the user gets every
    frame, each stream in its publisher's order."""
    import socket

    lane = 48   # 48 KB a user a tick: over what a stalled link's buffers take
    rounds = 1 if first_send == "whole" else 4
    broadcasts = [[(b"b%d.%d|" % (r, i)).ljust(1000, b".")
                   for i in range(lane)] for r in range(rounds)]
    directs = [[b"d%d.%d" % (r, i) for i in range(4)] for r in range(rounds)]
    calls = _record_batches(monkeypatch)
    async with _served_group(per_shard=1, ring_slots=lane) as (cluster,
                                                               clients):
        group = cluster.group
        publisher, user = clients[0], clients[1]    # shards 0 and 1
        others = [c for c in clients if c is not user]
        link = cluster.brokers[1].connections.get_user_connection(
            user.public_key)
        fd = _broker_fd(cluster.brokers[1], user)
        transport = link._stream.writer.transport
        stream = user._connection._stream
        if first_send == "short":
            stream.writer.get_extra_info("socket").setsockopt(
                socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
            link._stream.writer.get_extra_info("socket").setsockopt(
                socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
            stream.reader._transport.pause_reading()
        short = unbatched = 0
        for r in range(rounds):
            held, seen = transport.get_write_buffer_size(), len(calls)
            queued = group.egress_queued
            # the publisher's order on the wire, a direct after every
            # twelfth broadcast; one write, one receive batch, a full ring
            os.write(_socket_of(publisher), b"".join(
                _wire(b) + (_wire(directs[r][i // 12], to=user.public_key)
                            if i % 12 == 11 else b"")
                for i, b in enumerate(broadcasts[r])))
            assert await _receive_all(others, lane) == [broadcasts[r]] * 3
            await wait_until(lambda: group.egress_inline
                             + group.egress_queued == 5 * (r + 1))
            assert group.steps == r + 1
            mine = [(nbytes[at], sent[at])
                    for fds, nbytes, sent in calls[seen:] if fd in fds
                    for at in [fds.index(fd)]]
            if held:    # bytes on the fd would pass the transport's
                assert not mine
                unbatched += 1
                continue
            (b_bytes, b_sent), *second = mine
            assert b_sent <= b_bytes > 40_000
            short += b_sent < b_bytes
            if second:  # the transport took what was left of the first
                (d_bytes, d_sent), = second
                assert d_sent <= d_bytes < 1_000
            else:       # it holds some of it: the directs queue behind
                assert b_sent < b_bytes and link.idle_fd(1) is None
                assert group.egress_queued == queued + 1
        if first_send == "whole":
            assert not short
            assert (group.egress_batched, group.egress_queued) == (5, 0)
        else:
            assert short and unbatched
            stream.reader._transport.resume_reading()
        assert group.egress_batched == sum(len(c[0]) for c in calls)
        got, = await _receive_all([user], rounds * (lane + 4))
        assert [m for m in got if m.startswith(b"b")] == \
            [b for frames in broadcasts for b in frames]
        assert [m for m in got if m.startswith(b"d")] == \
            [d for frames in directs for d in frames]
        assert sum(b.connections.num_users for b in cluster.brokers) == 4
        assert not group.disabled


async def test_a_send_that_fails_in_the_groups_batch_removes_that_user_only(
        monkeypatch):
    """A job spans the shards, a user's link lives on one member: the
    failed send's user is removed there, and there only."""
    import socket

    gone = []   # the victim's peer is gone by the time of the send()

    def shut_down(fds):
        if gone:
            sock = socket.socket(fileno=os.dup(gone.pop()))
            sock.shutdown(socket.SHUT_RDWR)
            sock.close()
    calls = _record_batches(monkeypatch, shut_down)
    first = [b"first %d" % i for i in range(_RING)]
    second = [b"second %d" % i for i in range(_RING)]
    async with _served_group() as (cluster, clients):
        group = cluster.group
        victim = clients[5]     # shard 2
        others = [c for c in clients if c is not victim]
        gone.append(_broker_fd(cluster.brokers[2], victim))
        os.write(_socket_of(clients[0]), _wire(*first))
        assert await _receive_all(others, _RING) == [first] * 7
        assert not gone
        assert all(b.connections.get_user_connection(victim.public_key)
                   is None for b in cluster.brokers)
        assert [b.connections.num_users for b in cluster.brokers] == \
            [2, 2, 1, 2]
        assert (group.egress_batched, group.egress_inline,
                group.messages_routed) == (7, 7, 7 * _RING)
        # the next tick: the seven that are left, batched again
        os.write(_socket_of(clients[0]), _wire(*second))
        assert await _receive_all(others, _RING) == [second] * 7
        assert [len(fds) for fds, _, _ in calls] == [8, 7]
        assert group.egress_batched == 14 and not group.disabled


async def test_memory_users_of_a_back_pressured_tick_are_never_batched(
        monkeypatch):
    """The Memory transport has no socket (``idle_fd`` is ``None``): a
    tick that is back-pressured like any other goes one by one, every
    stream through its writer, and is delivered."""
    payloads = [b"frame %d" % i for i in range(_RING)]
    calls = _record_batches(monkeypatch)
    async with _served_group(user_protocol=Memory) as (cluster, clients):
        group = cluster.group
        conns = [cluster.brokers[u // 2].connections.get_user_connection(
            c.public_key) for u, c in enumerate(clients)]
        assert [c.idle_fd(1) for c in conns] == [None] * 8
        observed = []
        real = group._back_pressured

        def back_pressured():
            observed.append(real())
            return observed[-1]
        group._back_pressured = back_pressured
        # no socket to write into: one pipelined burst fills the ring
        await asyncio.gather(*(
            clients[0].send_broadcast_message([0], p) for p in payloads))
        got = await _receive_all(clients, _RING)
        assert got == [payloads] * 8
        assert observed == [True] and group.steps == 1
        assert group.egress_batched == 0 and not calls
        assert (group.egress_inline, group.egress_queued) == (0, 8)
        assert cluster.brokers[3].device_plane.describe()[
            "egress_batched"] == 0


# ---------------------------------------------------------------------------
# the batch's settling (ISSUE 38; ``DevicePlane``'s twin:
# tests/test_device_plane.py, "the batch's settling"): the group hands
# ``senders.egress_streams`` its members in a broker's place, so the one pass
# settles links that live on four brokers, and tallies on the group.
# ---------------------------------------------------------------------------

async def _settled_ticks(monkeypatch, per_link: bool):
    """Three back-pressured ticks (a full base ring of broadcasts to all
    eight users, then a full base bucket of directs to one, then both)
    through a four-shard group whose users keep up; what
    ``_egress_account`` moved by, and the ``sent_on_fd`` calls made."""
    from pushcdn_tpu.proto import ledger as ledger_mod

    one_by_one, _ = _watch_settling(monkeypatch)
    calls = _record_batches(monkeypatch, after=_never_whole) if per_link \
        else _record_batches(monkeypatch)
    broadcasts = [(b"b%d|" % i).ljust(40 + 7 * i, b".") for i in range(_RING)]
    directs = [(b"d%d|" % i).ljust(30 + 5 * i, b".") for i in range(_BUCKET)]
    async with _served_group() as (cluster, clients):
        group = cluster.group
        publisher, recipient = clients[0], clients[5]   # shards 0 and 2
        before = _egress_account(group)
        os.write(_socket_of(publisher), _wire(*broadcasts))
        assert await _receive_all(clients, _RING) == [broadcasts] * 8
        os.write(_socket_of(publisher),
                 _wire(*directs, to=recipient.public_key))
        assert await _receive_all([recipient], _BUCKET) == [directs]
        os.write(_socket_of(publisher), _wire(*broadcasts)
                 + _wire(*directs, to=recipient.public_key))
        got = await _receive_all(clients, _RING)
        assert [g[:_RING] for g in got] == [broadcasts] * 8
        await wait_until(lambda: group.messages_routed
                         == 2 * (8 * _RING + _BUCKET))
        assert group.steps == 3 and group.egress_queued == 0
        moved = _moved(before, _egress_account(group))
        book = ledger_mod.LEDGER
        assert book.walk_live_queues() == 0
        assert book.derived_in_queue() == [0] * len(book.queued)
        assert [b.device_plane.describe()["egress_batched_short"]
                for b in cluster.brokers] == [group.egress_batched_short] * 4
    assert all(sent == nbytes for _, nbytes, sent in calls)
    return moved, len(one_by_one), sum(len(fds) for fds, _, _ in calls)


async def test_the_groups_one_pass_leaves_what_settling_each_link_leaves(
        monkeypatch):
    from pushcdn_tpu.proto import flowclass

    bulk, bulk_calls, handoffs = await _settled_ticks(monkeypatch,
                                                      per_link=False)
    with monkeypatch.context() as patched:
        each, each_calls, same = await _settled_ticks(patched, per_link=True)
    assert handoffs == same == 2 * (8 + 1)
    assert (bulk_calls, each_calls) == (0, handoffs)
    assert bulk.pop(("plane", "egress_batched_short"), 0) == 0
    assert each.pop(("plane", "egress_batched_short")) == handoffs
    assert bulk == each
    frames, live = 2 * (8 * _RING + _BUCKET), flowclass.LIVE
    assert {k: v for k, v in bulk.items()
            if k[0] not in ("bytes_sent", "class_bytes_out")} == {
        ("class_frames_out", live): frames, ("queued", live): frames,
        ("fate", "delivered", "egress", live): frames,
        ("plane", "messages_routed"): frames,
        ("plane", "egress_inline"): handoffs,
        ("plane", "egress_batched"): handoffs}
    assert bulk[("bytes_sent", "tcp")] == bulk[("class_bytes_out", live)] > 0


async def test_the_groups_mixed_batch_settles_the_rest_each_on_its_member(
        monkeypatch):
    """One job over the four shards with a reader that has stopped (a
    short send, shard 1) and a peer that is gone (``EPIPE``, shard 2):
    those two are settled by ``sent_on_fd`` on the member that holds
    each, the six others in one pass; ``egress_batched_short`` counts the
    short one, the failed user leaves its own broker only."""
    import errno

    lane = 48   # 48 KB a user a tick: over what a stalled link's buffers take
    frames = [(b"f%d|" % i).ljust(1000, b".") for i in range(lane)]
    gone = []
    calls = _record_batches(monkeypatch, _shut_down_before_the_batch(gone))
    each, together = _watch_settling(monkeypatch)
    async with _served_group(ring_slots=lane) as (cluster, clients):
        group = cluster.group
        stalled, victim = clients[2], clients[5]    # shards 1 and 2
        others = [c for c in clients if c not in (stalled, victim)]
        links = {c: cluster.brokers[u // 2].connections.get_user_connection(
            c.public_key) for u, c in enumerate(clients)}
        _stall_reader(links[stalled], stalled)
        stalled_fd = _broker_fd(cluster.brokers[1], stalled)
        victim_fd = _broker_fd(cluster.brokers[2], victim)
        gone.append(victim_fd)
        os.write(_socket_of(clients[0]), _wire(*frames))
        assert await _receive_all(others, lane) == [frames] * 6
        (fds, nbytes, sent), = calls
        assert len(fds) == 8
        short_at, failed_at = fds.index(stalled_fd), fds.index(victim_fd)
        assert sent[failed_at] == -errno.EPIPE
        assert 0 <= sent[short_at] < nbytes[short_at] \
            or sent[short_at] == -errno.EAGAIN
        assert each == [(link, sent[at]) for at, link in sorted(
            [(short_at, links[stalled]), (failed_at, links[victim])])]
        (whole, _, whole_frames), = together
        assert sorted(map(id, whole)) == sorted(id(links[c]) for c in others)
        assert whole_frames == [lane] * 6
        assert (group.egress_batched, group.egress_batched_short,
                group.egress_inline, group.messages_routed) == \
            (7, 1, 7, 7 * lane)
        assert [b.connections.num_users for b in cluster.brokers] == \
            [2, 2, 1, 2]
        stalled._connection._stream.reader._transport.resume_reading()
        got, = await _receive_all([stalled], lane)
        assert got == frames and not group.disabled
