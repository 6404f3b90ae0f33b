"""Worker for the two-process multi-host KILL test (run via subprocess).

Same deployment shape as ``_multihost_worker.py`` (jax.distributed, global
8-shard mesh, one TCP broker + marshal + client per OS process, zero host
broker links), but the scenario is a mid-stream host death:

- both ranks prove the device plane end to end (cross-host broadcast),
  then touch a ``ready-<rank>`` sentinel file;
- the parent SIGKILLs rank 1;
- rank 0 (the survivor, also the jax coordinator) must observe the
  collective fail, see the group disable itself CLEANLY (pump task
  finished — no hung collective), and keep serving its local client over
  the host path (direct echo + local broadcast), then print ``KILL OK``.

Parity: the reference self-heals its host mesh from any peer death within
one heartbeat tick (cdn-broker/src/tasks/broker/heartbeat.rs:69-107); an
SPMD collective group cannot self-heal mid-world (every step needs every
process), so the contract here is fail-CLOSED on the device plane,
fail-OPEN for local host-path service, and recovery by redeployment (the
parent test's phase 2 — jax.distributed's world is static, so "the
restarted host rejoins" happens at deployment granularity).

Usage: _multihost_kill_worker.py <rank> <base_port> <db_path> <tmp_dir>
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
tmp = sys.argv[4]

# a generous heartbeat window: when the peer is SIGKILLed, the
# coordination service's error-poller TERMINATES surviving processes
# (client.h LOG(FATAL) — jax's by-design SPMD restart posture). The
# survivor needs to outlive the GLOO collective failure long enough to
# assert its clean-halt and host-path-service guarantees and exit on its
# own terms.
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{base}",
                           num_processes=2, process_id=rank,
                           heartbeat_timeout_seconds=600)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pushcdn_tpu.proto.crypto.signature import DEFAULT_SCHEME  # noqa: E402
from pushcdn_tpu.proto.message import Broadcast, Direct  # noqa: E402
from pushcdn_tpu.testing.two_host import make_two_host_node  # noqa: E402

CLIENT_SEED = [71_000, 72_000]


async def main() -> None:
    try:
        await _main()
    except BaseException:
        # fail INSIDE the coroutine: asyncio.run's finally would join the
        # default executor, and a collective thread stuck in gloo would
        # turn any assert failure into a silent minutes-long hang
        import traceback
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)


async def _main() -> None:
    node = await make_two_host_node(
        rank, base, db, client_seeds=CLIENT_SEED, broker_seed_base=80)
    group, broker, client = node.group, node.broker, node.client
    my_shard = node.my_shard

    # rendezvous via the user-slot directory
    await node.directory_rendezvous()

    # prove the device plane is live end to end before the kill
    if rank == 0:
        await client.send_broadcast_message([0], b"pre-kill hello")
    got = await asyncio.wait_for(client.receive_message(), 60)
    assert isinstance(got, Broadcast) and bytes(got.message) == b"pre-kill hello"
    assert broker.connections.num_brokers == 0

    with open(os.path.join(tmp, f"ready-{rank}"), "w") as f:
        f.write("ready")

    if rank == 1:
        # sit in the collective pump until the parent SIGKILLs us
        await asyncio.sleep(3600)
        return

    # ---- rank 0: survive the peer's death --------------------------------
    # the next collective step must FAIL (dead peer), the pump must exit
    # cleanly, and the group must disable itself
    for _ in range(1500):  # up to 150 s: gloo/coordination detection time
        if group.disabled:
            break
        await asyncio.sleep(0.1)
    assert group.disabled, "peer death never disabled the group"
    print("MARK: disabled", flush=True)
    # clean halt: the pump task RETURNED (no hung collective). When the
    # STEP (rather than the stop-barrier) is what caught the death, the
    # pump still runs its bounded last-barrier (<= collective_timeout_s)
    # before returning — poll past that bound.
    for _ in range(450):
        if group._task is None or group._task.done():
            break
        await asyncio.sleep(0.1)
    assert group._task is None or group._task.done(), \
        "pump still running after disable (hung collective?)"
    print("MARK: pump done", flush=True)

    # staging now fail-fasts instead of blackholing
    from pushcdn_tpu.broker.staging import StageResult
    from pushcdn_tpu.proto.limiter import Bytes as _Bytes
    from pushcdn_tpu.proto.message import serialize
    late = Broadcast(topics=[0], message=b"late")
    assert group.try_stage(my_shard, late, _Bytes(serialize(late))) == \
        StageResult.INELIGIBLE
    print("MARK: stage fail-fast", flush=True)

    # the survivor KEEPS SERVING local clients over the host path
    own_pk = DEFAULT_SCHEME.generate_keypair(seed=CLIENT_SEED[0]).public_key
    print("MARK: sending direct", flush=True)
    await client.send_direct_message(own_pk, b"still served")
    print("MARK: direct sent", flush=True)
    got = await asyncio.wait_for(client.receive_message(), 30)
    assert isinstance(got, Direct) and bytes(got.message) == b"still served"
    await client.send_broadcast_message([0], b"local fanout works")
    got = await asyncio.wait_for(client.receive_message(), 30)
    assert isinstance(got, Broadcast) and \
        bytes(got.message) == b"local fanout works"
    assert broker.connections.num_users == 1

    client.close()
    await node.marshal.stop()
    await broker.stop()
    print(f"rank {rank}: KILL OK (steps={group.steps}, disabled clean)",
          flush=True)
    # skip jax.distributed.shutdown(): its barrier would wait forever for
    # the killed peer (and so would the atexit hook) — hard-exit instead
    os._exit(0)


asyncio.run(main())
