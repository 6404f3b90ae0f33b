"""Worker for the two-process SPMD test (run via subprocess, not pytest).

Each of two OS processes hosts 4 virtual CPU devices, joins the
jax.distributed runtime, builds the SAME global 8-shard broker mesh, and
executes ONE jitted lane step collectively — the real multi-host
contract (pushcdn_tpu/parallel/multihost.py), not the single-process
8-device pretend version. Asserts, per process:

- the runtime really is 2 processes x 4 local devices;
- the broker-axis ring crosses DCN exactly twice;
- frames published on the OTHER process's shards deliver to THIS
  process's users (cross-process fan-out through the all_gather);
- every shard's direct frame lands exactly once at its owner shard
  (all_to_all across the process boundary);
- the CRDT converges: claims seeded only on remote shards appear in
  this process's merged owner table.

Usage: _spmd_worker.py <rank> <coordinator_port>
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

rank = int(sys.argv[1])
port = int(sys.argv[2])

jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=rank)

assert jax.process_count() == 2, jax.process_count()
assert jax.local_device_count() == 4, jax.local_device_count()
assert jax.device_count() == 8, jax.device_count()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pushcdn_tpu.parallel.crdt import ABSENT, CrdtState  # noqa: E402
from pushcdn_tpu.parallel.frames import DirectBuckets, FrameRing  # noqa: E402
from pushcdn_tpu.parallel.multihost import (  # noqa: E402
    dcn_crossings,
    local_shard_indices,
    pod_broker_mesh,
)
from pushcdn_tpu.parallel.router import (  # noqa: E402
    BROKER_AXIS,
    DirectIngress,
    IngressBatch,
    RouterState,
    make_mesh_lane_step,
)

N = 8      # global shards
U = 16     # user slots per shard

mesh = pod_broker_mesh(N)
assert dcn_crossings(mesh) == 2, dcn_crossings(mesh)
local = local_shard_indices(mesh)
expected_local = list(range(4)) if rank == 0 else list(range(4, 8))
assert local == expected_local, (rank, local)

step = make_mesh_lane_step(mesh)


def garr(host_array):
    """Global sharded array from identical per-process host data."""
    return jax.make_array_from_callback(
        host_array.shape, NamedSharding(mesh, P(BROKER_AXIS)),
        lambda idx: host_array[idx])


# CRDT seed: shard i claims user slot i — each claim exists ONLY on its
# origin shard's row, so convergence requires the cross-process merge.
owners = np.full((N, U), ABSENT, np.int32)
versions = np.zeros((N, U), np.uint32)
ids = np.full((N, U), ABSENT, np.int32)
masks = np.zeros((N, U), np.uint32)
for i in range(N):
    owners[i, i] = i
    versions[i, i] = 1
    ids[i, i] = i
    masks[i, i] = 0b1

state = RouterState(
    CrdtState(garr(owners), garr(versions), garr(ids)), garr(masks))

# one broadcast frame per shard (topic bit 0), one direct frame per shard
# addressed to user slot (i+1) % N — owned by the NEXT shard, so rank 0's
# shard 3 sends across the process boundary to rank 1's shard 4, etc.
ring_parts = []
for i in range(N):
    r = FrameRing(slots=4, frame_bytes=64)
    r.push_broadcast(b"from-%d" % i, 0b1)
    ring_parts.append(r.take_batch())
S = ring_parts[0].kind.shape[0]
batch = IngressBatch(
    garr(np.stack([p.bytes_ for p in ring_parts])),
    garr(np.stack([p.kind for p in ring_parts])),
    garr(np.stack([p.length for p in ring_parts])),
    garr(np.stack([p.topic_mask for p in ring_parts])),
    garr(np.stack([p.dest for p in ring_parts])),
    garr(np.stack([p.valid for p in ring_parts])))

dparts = []
for i in range(N):
    d = DirectBuckets(N, capacity=2, frame_bytes=128)
    d.push((i + 1) % N, b"direct-%d" % i, dest_slot=(i + 1) % N)
    dparts.append(d.take_batch())
direct = DirectIngress(
    garr(np.stack([p.bytes_ for p in dparts])),
    garr(np.stack([p.length for p in dparts])),
    garr(np.stack([p.dest for p in dparts])),
    garr(np.stack([p.valid for p in dparts])))

out = step(state, (batch,), (direct,))

# ---- global invariants (replicated scalars, addressable everywhere) ----
lane_total = int(jnp.sum(out.lanes[0].deliver))
assert lane_total == N * N, lane_total          # every frame -> every user
direct_total = int(jnp.sum(out.direct_lanes[0].deliver))
assert direct_total == N, direct_total          # one landing per frame

# ---- per-process (cross-process) assertions ----------------------------
remote = set(range(N)) - set(local)
for shard in out.lanes[0].deliver.addressable_shards:
    b = shard.index[0].start  # this device's broker index
    dm = np.asarray(shard.data)[0]  # [U, N*S] (users x gathered frames)
    # frames are gathered as src*S + slot; count deliveries whose source
    # shard lives on the OTHER process
    from_remote = sum(int(dm[:, src * S].sum()) for src in remote)
    assert from_remote == len(remote), (b, from_remote)

for shard in out.state.crdt.owners.addressable_shards:
    merged = np.asarray(shard.data)[0]  # [U]
    for i in range(N):
        assert merged[i] == i, (i, merged[:N])  # remote claims arrived

# direct: this process's shards each received exactly the one frame
# addressed to them, sent by the PREVIOUS shard (cross-process for the
# boundary shards 0 and 4)
for shard in out.direct_lanes[0].deliver.addressable_shards:
    b = shard.index[0].start
    dm = np.asarray(shard.data)[0]
    assert int(dm.sum()) == 1, (b, dm.sum())

jax.distributed.shutdown()
print(f"rank {rank}: SPMD OK (process_count=2, dcn_crossings=2, "
      f"cross-process deliveries + CRDT convergence verified)")
