"""Worker for the two-process multi-host PERMANENT-STALL test (run via
subprocess). The kill test (``_multihost_kill_worker.py``) covers a peer
that DIES; this covers the nastier failure VERDICT r5 #6 asked for — a
peer that is alive but never progresses (wedged runtime, livelocked step
thread, GC death spiral): the OS gives no connection-reset signal, so
only the survivor's own collective watchdog can bound detection.

- both ranks prove the device plane end to end (cross-host broadcast),
  then touch a ``ready-<rank>`` sentinel file;
- rank 1 then injects a PERMANENT block into its collective tick (the
  straggler bench's delay injection with an unbounded delay) and sits
  there — the process stays alive, sockets open, heartbeats flowing;
- rank 0 must observe its collective watchdog (``collective_timeout_s``)
  fire, see the group fail CLOSED (disabled, pump task returned —
  no hung collective), fail-fast staging, keep serving its local client
  over the host path, then print ``STALL OK`` and exit 0;
- the parent test kills the stalled rank afterwards and redeploys a
  FRESH two-process group (phase 2) — recovery is redeployment without
  the stalled host, same posture as the kill test.

Usage: _multihost_stall_worker.py <rank> <base_port> <db_path> <tmp_dir>
"""

import asyncio
import os
import sys
import threading
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402

rank = int(sys.argv[1])
base = int(sys.argv[2])
db = sys.argv[3]
tmp = sys.argv[4]

# generous heartbeat window, same reasoning as the kill worker: the
# survivor must outlive the collective failure long enough to assert its
# guarantees before the coordination service's posture can matter
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{base}",
                           num_processes=2, process_id=rank,
                           heartbeat_timeout_seconds=600)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pushcdn_tpu.broker.mesh_group import MeshGroupConfig  # noqa: E402
from pushcdn_tpu.proto.crypto.signature import DEFAULT_SCHEME  # noqa: E402
from pushcdn_tpu.proto.message import Broadcast, Direct  # noqa: E402
from pushcdn_tpu.testing.two_host import make_two_host_node  # noqa: E402

CLIENT_SEED = [73_000, 74_000]
WATCHDOG_S = 20.0


async def main() -> None:
    try:
        await _main()
    except BaseException:
        # fail INSIDE the coroutine (see the kill worker): asyncio.run's
        # finally would join the executor and a collective thread stuck in
        # gloo turns an assert failure into a silent hang
        import traceback
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)


async def _main() -> None:
    node = await make_two_host_node(
        rank, base, db, client_seeds=CLIENT_SEED, broker_seed_base=85,
        mesh_config=MeshGroupConfig(
            num_user_slots=64, ring_slots=64, frame_bytes=2048,
            extra_lanes=(), direct_bucket_slots=4,
            batch_window_s=0.02),
        collective_timeout_s=WATCHDOG_S)
    group, broker, client = node.group, node.broker, node.client
    my_shard = node.my_shard

    await node.directory_rendezvous()

    # prove the device plane is live end to end before the stall
    if rank == 0:
        await client.send_broadcast_message([0], b"pre-stall hello")
    got = await asyncio.wait_for(client.receive_message(), 60)
    assert isinstance(got, Broadcast) and \
        bytes(got.message) == b"pre-stall hello"
    assert broker.connections.num_brokers == 0

    with open(os.path.join(tmp, f"ready-{rank}"), "w") as f:
        f.write("ready")

    if rank == 1:
        # the PERMANENT stall: every collective tick blocks forever from
        # here on. The process stays alive (this is the difference from
        # SIGKILL — no FIN, no connection reset, heartbeat threads keep
        # running); only the survivor's watchdog can detect it.
        stalled = threading.Event()

        def stall_forever(_want_stop):
            stalled.set()
            while True:  # never returns, never raises
                time.sleep(3600)

        group._collective_stop = stall_forever
        # wait out the parent's kill; prove we were genuinely reached
        while not stalled.is_set():
            await asyncio.sleep(0.1)
        print("rank 1: STALLED (alive, wedged in collective)", flush=True)
        await asyncio.sleep(3600)
        return

    # ---- rank 0: survive the peer's livelock -----------------------------
    # the watchdog must fail the group CLOSED within ~collective_timeout_s
    # (plus one tick); poll to 3x the bound before declaring failure
    t0 = time.monotonic()
    while time.monotonic() - t0 < 3 * WATCHDOG_S + 30:
        if group.disabled:
            break
        await asyncio.sleep(0.1)
    assert group.disabled, \
        f"stalled peer never tripped the watchdog within {3 * WATCHDOG_S + 30}s"
    detect_s = time.monotonic() - t0
    print(f"MARK: disabled after {detect_s:.1f}s (watchdog {WATCHDOG_S}s)",
          flush=True)
    # clean halt: the pump task RETURNED (its own last-barrier is bounded
    # by the same watchdog) — no hung collective
    for _ in range(int((WATCHDOG_S + 25) * 10)):
        if group._task is None or group._task.done():
            break
        await asyncio.sleep(0.1)
    assert group._task is None or group._task.done(), \
        "pump still running after disable (hung collective?)"
    print("MARK: pump done", flush=True)

    # staging fail-fasts instead of blackholing
    from pushcdn_tpu.broker.staging import StageResult
    from pushcdn_tpu.proto.limiter import Bytes as _Bytes
    from pushcdn_tpu.proto.message import serialize
    late = Broadcast(topics=[0], message=b"late")
    assert group.try_stage(my_shard, late, _Bytes(serialize(late))) == \
        StageResult.INELIGIBLE
    print("MARK: stage fail-fast", flush=True)

    # host-path service continues for local clients
    own_pk = DEFAULT_SCHEME.generate_keypair(seed=CLIENT_SEED[0]).public_key
    await client.send_direct_message(own_pk, b"still served")
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
    print(f"rank {rank}: STALL OK (detected in {detect_s:.1f}s, "
          f"steps={group.steps}, disabled clean)", flush=True)
    # skip jax.distributed.shutdown(): its barrier would gate on the
    # stalled peer forever — hard-exit instead
    os._exit(0)


asyncio.run(main())
