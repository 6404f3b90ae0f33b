"""MultiHostBrokerGroup unit tier on the single-process degenerate case
(process_count == 1 ⇒ every shard is local): the partitioned slot space,
the discovery user-slot directory lifecycle, same-host cross-shard
reconnect kicks, and the lockstep pump routing real traffic — all
without subprocesses (the two-OS-process deployment test covers the
cross-host paths)."""

import asyncio

from pushcdn_tpu.broker.mesh_group import MeshGroupConfig
from pushcdn_tpu.broker.multihost_group import (
    MultiHostBrokerGroup,
    PartitionedUserSlots,
)
from pushcdn_tpu.parallel.mesh import make_broker_mesh
from pushcdn_tpu.proto.discovery.embedded import Embedded
from pushcdn_tpu.proto.error import Error


def test_partitioned_slots_owner_by_construction():
    slots = PartitionedUserSlots(64, num_shards=4, local_shards=[1, 3])
    a = slots.assign_in_shard(b"alice", 1)
    b = slots.assign_in_shard(b"bob", 3)
    assert a // slots.slots_per_shard == 1
    assert b // slots.slots_per_shard == 3
    # re-claim at the same shard returns the same slot
    assert slots.assign_in_shard(b"alice", 1) == a
    # freed slots return to their OWN shard's range
    slots.unmap(b"alice")
    slots.free_slot(a)
    assert slots.assign_in_shard(b"carol", 1) == a
    # a non-local shard has no free list
    try:
        slots.assign_in_shard(b"dave", 0)
        raise AssertionError("non-local shard must not allocate")
    except Error:
        pass
    # exhaustion of one shard's range is typed, not silent
    K = slots.slots_per_shard
    for i in range(K - 1):  # carol already holds one
        slots.assign_in_shard(b"u%d" % i, 1)
    try:
        slots.assign_in_shard(b"overflow", 1)
        raise AssertionError("full range must bail")
    except Error:
        pass


async def test_single_process_group_routes_and_directory(tmp_path):
    import jax

    db = str(tmp_path / "d.sqlite")
    mesh = make_broker_mesh(4, devices=jax.devices("cpu")[:4])
    group = MultiHostBrokerGroup(
        mesh,
        MeshGroupConfig(num_user_slots=32, ring_slots=8, frame_bytes=512,
                        extra_lanes=(), direct_bucket_slots=4,
                        batch_window_s=0.02),
        discovery=await Embedded.new(db),
        directory_refresh_s=0.1)
    assert group.local_shards == [0, 1, 2, 3]

    class FakeUserConnection:
        encrypts = False  # a plain link: no clock, no ``egress_tls``

        def __init__(self):
            self.streams = []

        def try_send_encoded_inline(self, data, cls=2, nframes=0):
            return False  # never idle: every stream takes the queued call

        def send_encoded_nowait(self, data, owner=None, cls=2, nframes=0):
            self.streams.append(bytes(data))

    class FakeConnections:
        """Mirrors the real Connections contract the group depends on:
        remove_user fires the observer's on_user_removed synchronously
        (that is what releases the old slot during a kick), and egress
        looks sessions up via get_user_connection."""

        def __init__(self):
            self.removed = []
            self.users = {}
            self.observer = None

        def has_user(self, pk):
            return bytes(pk) in self.users

        def get_user_connection(self, pk):
            return self.users.get(bytes(pk))

        def remove_user(self, pk, reason=""):
            self.removed.append((bytes(pk), reason))
            self.users.pop(bytes(pk), None)
            if self.observer is not None:
                self.observer.on_user_removed(bytes(pk))

    class FakeBroker:
        def __init__(self, ident):
            self.identity = ident
            self.connections = FakeConnections()
            self.host_links_kick = asyncio.Event()

        def update_metrics(self):
            pass

    brokers = [FakeBroker("mhg-b0"), FakeBroker("mhg-b2")]
    # attach without the Broker class: the group only needs connections +
    # identity + host_links_kick
    planes = [group.attach(brokers[0], 0), group.attach(brokers[1], 2)]
    for fb, plane in zip(brokers, planes):
        fb.connections.observer = plane
    try:
        await group.ensure_started()

        # claims land in the claiming shard's range and publish to the
        # directory on refresh (sessions register like real connections)
        alice_conn, bob_conn = FakeUserConnection(), FakeUserConnection()
        brokers[0].connections.users[b"alice-pk"] = alice_conn
        group.claim_user(0, b"alice-pk", [0])
        brokers[1].connections.users[b"bob-pk"] = bob_conn
        group.claim_user(2, b"bob-pk", [0])
        slot_a = group.slots.slot_of(b"alice-pk")
        assert slot_a // group.slots_per_shard == 0
        for _ in range(50):
            d = await group.discovery.get_user_slots()
            if b"alice-pk" in d and b"bob-pk" in d:
                break
            await asyncio.sleep(0.05)
        else:
            raise AssertionError("directory never converged")

        # directs resolve the owner statically from the slot
        info = group._direct_route_info(b"bob-pk")
        assert info is not None and info[1] == 2

        # the lockstep pump ROUTES: a broadcast staged at shard 0 lands
        # at both subscribers' sessions as pre-framed egress streams
        from pushcdn_tpu.broker.staging import StageResult
        from pushcdn_tpu.proto.limiter import Bytes
        from pushcdn_tpu.proto.message import Broadcast, serialize
        wire = serialize(Broadcast(topics=[0], message=b"lockstep!"))
        res = planes[0].try_stage(Broadcast(topics=[0], message=b"lockstep!"),
                                  Bytes(wire))
        assert res == StageResult.STAGED
        for _ in range(100):
            if alice_conn.streams and bob_conn.streams:
                break
            await asyncio.sleep(0.05)
        else:
            raise AssertionError("lockstep pump never delivered")
        # the stream is the wire frame, u32-BE length-prefixed
        for conn in (alice_conn, bob_conn):
            frame = conn.streams[0]
            assert frame[4:] == wire and                 int.from_bytes(frame[:4], "big") == len(wire)
        assert group.steps >= 1 and group.messages_routed >= 2

        # same-host cross-shard reconnect: the old session is kicked
        # (observer releases its slot) and the claim moves to shard 2's
        # range in ONE call, exactly like a real reconnect
        brokers[1].connections.users[b"alice-pk"] = FakeUserConnection()
        group.claim_user(2, b"alice-pk", [0])
        assert (b"alice-pk", "user connected elsewhere") in \
            brokers[0].connections.removed
        new_slot = group.slots.slot_of(b"alice-pk")
        assert new_slot // group.slots_per_shard == 2

        # release drops the directory entry (we own the claim)
        group.release_user(2, b"bob-pk")
        for _ in range(50):
            if b"bob-pk" not in await group.discovery.get_user_slots():
                break
            await asyncio.sleep(0.05)
        else:
            raise AssertionError("release never dropped the claim")

        assert not group.disabled

        # partial retirement: one of the host's brokers stops — the
        # collective keeps running (other local brokers depend on it)
        await group.on_shard_stopped(0)
        assert group._task is not None and not group._stop_requested
        assert not group.disabled
        # shard 2 still routes: a direct to bob from shard 2 delivers
        bob_conn2 = FakeUserConnection()
        brokers[1].connections.users[b"bob-pk"] = bob_conn2
        group.claim_user(2, b"bob-pk", [0])
        wire2 = serialize(Broadcast(topics=[0], message=b"after partial"))
        assert planes[1].try_stage(
            Broadcast(topics=[0], message=b"after partial"),
            Bytes(wire2)) == StageResult.STAGED
        for _ in range(100):
            if bob_conn2.streams:
                break
            await asyncio.sleep(0.05)
        else:
            raise AssertionError("group stopped routing after a partial "
                                 "host retirement")
    finally:
        await group.on_shard_stopped(0)
        await group.on_shard_stopped(2)
        await group.discovery.close()
