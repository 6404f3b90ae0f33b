"""Multi-host broker meshes: ICI within a slice, DCN across hosts.

The reference scales across machines with per-peer TCP links coordinated
by the discovery registry (SURVEY.md §1-L0/L5). The TPU-native equivalent
is a **global device mesh spanning every host's chips**: jax's runtime is
SPMD — every host process runs the same jitted routing step over the same
global mesh, XLA partitions the collectives so the all_gather/all_to_all
hops ride ICI inside each slice and DCN only where the mesh crosses
slices. No NCCL/MPI and no per-peer socket code: the collective IS the
inter-broker transport (BASELINE.json north star).

Deployment contract (mirrors jax.distributed):

1. every host calls :func:`initialize` with the same coordinator address
   and its own ``process_id`` (on Cloud TPU all three args are inferred);
2. every host builds the same global mesh via :func:`pod_broker_mesh`;
3. each host's brokers attach only to its LOCAL shards
   (:func:`local_shard_indices`) — users terminate at the host that owns
   their shard, exactly like the reference pinning a user to one broker;
4. every host participates in every step (SPMD): the per-shard CRDT
   claims diverge across hosts and the in-step merge converges them —
   the device program is identical to the single-host one
   (pushcdn_tpu.parallel.router), which is why the single-host group
   property-tests stand in for pod behavior.

Mesh geometry: :func:`pod_broker_mesh` keeps jax's default device order,
which walks each process's devices consecutively — so the broker axis is
contiguous per host and ICI neighbors stay mesh neighbors; the all_gather
ring crosses DCN exactly (num_hosts) times per step, the minimum any
all-host exchange can do.
"""

from __future__ import annotations

from typing import List, Optional

import jax
from jax.sharding import Mesh

from pushcdn_tpu.parallel.mesh import make_broker_mesh


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Join the multi-host runtime (idempotent). On Cloud TPU all args are
    auto-detected; elsewhere pass the coordinator's ``host:port``, the
    process count, and this process's rank — the same contract as the
    reference's discovery endpoint + broker identity pair."""
    if jax.distributed.is_initialized():
        return  # idempotent: already joined (explicit or auto)
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    try:
        jax.distributed.initialize(**kwargs)
    except (RuntimeError, ValueError):
        if kwargs:
            raise  # an explicit join that failed is a real error
        # bare call with nothing to auto-detect (off-pod: ValueError) or
        # after the backend already started (RuntimeError): single-process
        # runtime, nothing to join


def pod_broker_mesh(num_brokers: Optional[int] = None) -> Mesh:
    """The GLOBAL broker mesh over every host's devices. Must be called
    with identical arguments on every process (SPMD).

    ``num_brokers`` may not exclude a whole host: jax's device order is
    process-contiguous, so truncating past a host boundary would leave
    that process with zero local shards in a mesh it must still execute
    collectively — a guaranteed hang or failure. Use every host or run a
    smaller deployment.
    """
    mesh = make_broker_mesh(num_brokers, devices=jax.devices())
    covered = {d.process_index for d in mesh.devices.flat}
    if len(covered) != jax.process_count():
        from pushcdn_tpu.proto.error import ErrorKind, bail
        bail(ErrorKind.PARSE,
             f"num_brokers={num_brokers} covers only {len(covered)} of "
             f"{jax.process_count()} host processes; every SPMD process "
             "needs at least one local shard")
    return mesh


def local_shard_indices(mesh: Mesh) -> List[int]:
    """Broker-shard indices whose device lives on THIS host — the shards
    this process's brokers may attach to (users terminate here)."""
    me = jax.process_index()
    return [i for i, d in enumerate(mesh.devices.flat)
            if d.process_index == me]


def dcn_crossings(mesh: Mesh) -> int:
    """How many times the broker-axis ring crosses a host boundary — the
    per-step DCN hop count of the all_gather (diagnostic; minimal when
    each host's devices are contiguous on the axis)."""
    devs = list(mesh.devices.flat)
    return sum(1 for a, b in zip(devs, devs[1:] + devs[:1])
               if a.process_index != b.process_index)
