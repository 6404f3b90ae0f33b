"""Worker for the two-process multi-host DEPLOYMENT test (run via
subprocess, not pytest).

Each of two OS processes hosts 4 virtual CPU devices and runs a REAL
slice of the system — jax.distributed runtime, the global 8-shard broker
mesh, its own marshal (stateless, parity: many marshals per deployment),
one TCP broker attached to a local mesh shard (``form_mesh=False``: no
host broker links ever form), and one TCP client authenticated through
its marshal. Asserts the VERDICT deployment criterion end to end:

- a broadcast published by host 0's client is delivered to host 1's
  client purely over the device mesh (zero host broker links on both
  sides, checked);
- a direct message from host 1's client to host 0's client routes
  cross-host after the discovery user-slot directory propagates;
- both brokers report ``connections.num_brokers == 0`` throughout.

Usage: _multihost_worker.py <rank> <base_port> <discovery_db_path>
"""

import asyncio
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402

rank = int(sys.argv[1])
base = int(sys.argv[2])
db = sys.argv[3]

jax.distributed.initialize(coordinator_address=f"127.0.0.1:{base}",
                           num_processes=2, process_id=rank)
assert jax.process_count() == 2

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pushcdn_tpu.proto.crypto.signature import DEFAULT_SCHEME  # noqa: E402
from pushcdn_tpu.proto.message import Broadcast, Direct  # noqa: E402
from pushcdn_tpu.testing.two_host import make_two_host_node  # noqa: E402

# deterministic client identities: each host can derive the OTHER's key
CLIENT_SEED = [61_000, 62_000]


async def main() -> None:
    node = await make_two_host_node(
        rank, base, db, client_seeds=CLIENT_SEED, broker_seed_base=50)
    group, broker, client = node.group, node.broker, node.client
    my_shard = node.my_shard

    # rendezvous: wait until the user-slot directory shows BOTH clients
    # (this also phase-syncs the two processes)
    await node.directory_rendezvous()

    # ---- cross-host broadcast (the VERDICT 'Done' criterion) -------------
    if rank == 0:
        await client.send_broadcast_message([0], b"cross-host hello")
    got = await asyncio.wait_for(client.receive_message(), 30)
    assert isinstance(got, Broadcast), got
    assert bytes(got.message) == b"cross-host hello"
    assert broker.connections.num_brokers == 0  # zero host broker links

    # ---- cross-host direct (via the slot directory) ----------------------
    peer_pk = DEFAULT_SCHEME.generate_keypair(
        seed=CLIENT_SEED[1 - rank]).public_key
    # directs are fire-and-forget (reference parity): wait until THIS
    # host's directory mirror has the peer's slot before sending, or the
    # frame legitimately drops as unroutable
    for _ in range(100):
        if group._direct_route_info(bytes(peer_pk)) is not None:
            break
        await asyncio.sleep(0.1)
    else:
        raise AssertionError("peer slot never reached the local mirror")
    if rank == 1:
        await client.send_direct_message(peer_pk, b"direct across hosts")
        # host 0 answers so BOTH directions are proven
        got = await asyncio.wait_for(client.receive_message(), 30)
        assert isinstance(got, Direct)
        assert bytes(got.message) == b"ack from host 0"
    else:
        got = await asyncio.wait_for(client.receive_message(), 30)
        assert isinstance(got, Direct), got
        assert bytes(got.message) == b"direct across hosts"
        await client.send_direct_message(peer_pk, b"ack from host 0")

    assert broker.connections.num_brokers == 0
    assert group.steps > 0
    assert not group.disabled

    # end-of-test rendezvous: neither host may stop the collective pump
    # until BOTH have seen their final deliveries (the directory doubles
    # as the phase barrier)
    await node.publish_marker(b"done-%d" % rank)
    await node.await_markers([b"done-0", b"done-1"])

    client.close()
    await node.marshal.stop()
    if rank == 0:
        await broker.stop()   # triggers the collective stop barrier
    else:
        # peer retirement must stop the collective HERE too (same barrier
        # iteration) and flip disabled, so staging fail-fasts instead of
        # ACKing frames into rings nothing will ever drain
        for _ in range(200):
            if group.disabled:
                break
            await asyncio.sleep(0.05)
        assert group.disabled, "peer retirement never disabled the group"
        from pushcdn_tpu.broker.staging import StageResult
        from pushcdn_tpu.proto.limiter import Bytes as _Bytes
        from pushcdn_tpu.proto.message import serialize
        late = Broadcast(topics=[0], message=b"late")
        raw = _Bytes(serialize(late))
        assert group.try_stage(my_shard, late, raw) == \
            StageResult.INELIGIBLE
        await broker.stop()
    await group.discovery.close()
    jax.distributed.shutdown()
    print(f"rank {rank}: MULTIHOST OK (steps={group.steps}, "
          f"routed={group.messages_routed}, host_links=0)")


asyncio.run(main())
