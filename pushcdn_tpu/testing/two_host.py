"""Shared machinery for the two-OS-process deployment harnesses.

Three subprocess workers (tests/_multihost_worker.py,
tests/_multihost_kill_worker.py, benches/_straggler_worker.py) drive the
same deployment shape — jax.distributed runtime, global 8-shard mesh,
one TCP broker attached to a local shard, a stateless marshal pinned to
that broker, one authenticated TCP client — and their parents share one
spawn/collect harness. Both halves live here so a deployment-shape
change lands once (the copies had already drifted on ring/frame sizes
before this extraction).

Import ONLY after ``jax.distributed.initialize`` has run in the worker
process (the mesh helpers read the initialized process topology).
"""

from __future__ import annotations

import asyncio
import os
import subprocess
import sys
from dataclasses import dataclass
from typing import List, Optional

from pushcdn_tpu.broker.broker import Broker, BrokerConfig
from pushcdn_tpu.broker.mesh_group import MeshGroupConfig
from pushcdn_tpu.broker.multihost_group import MultiHostBrokerGroup
from pushcdn_tpu.client import Client, ClientConfig
from pushcdn_tpu.marshal import Marshal, MarshalConfig
from pushcdn_tpu.parallel.multihost import (
    local_shard_indices,
    pod_broker_mesh,
)
from pushcdn_tpu.proto.crypto.signature import DEFAULT_SCHEME
from pushcdn_tpu.proto.def_ import testing_run_def
from pushcdn_tpu.proto.discovery.base import BrokerIdentifier
from pushcdn_tpu.proto.discovery.embedded import Embedded
from pushcdn_tpu.proto.transport import Tcp
from pushcdn_tpu.testing.ports import free_port_block

N_SHARDS = 8


@dataclass
class TwoHostNode:
    """One process's slice of the two-host deployment."""

    rank: int
    my_shard: int
    ident: BrokerIdentifier
    group: MultiHostBrokerGroup
    broker: Broker
    marshal: Marshal
    client: Client

    async def directory_rendezvous(self, want: int = 2,
                                   timeout_s: float = 20.0) -> None:
        """Wait until the user-slot directory shows ``want`` clients —
        the standard phase barrier between the two processes."""
        for _ in range(int(timeout_s / 0.1)):
            if len(await self.group.discovery.get_user_slots()) >= want:
                return
            await asyncio.sleep(0.1)
        raise AssertionError("user-slot directory never converged")

    async def publish_marker(self, marker: bytes) -> None:
        await self.group.discovery.publish_user_slots({marker: (0, 0.0)}, 60)

    async def await_markers(self, markers: List[bytes],
                            timeout_s: float = 20.0) -> None:
        for _ in range(int(timeout_s / 0.1)):
            slots = await self.group.discovery.get_user_slots()
            if all(m in slots for m in markers):
                return
            await asyncio.sleep(0.1)
        raise AssertionError(f"markers {markers} never all appeared")


async def make_two_host_node(rank: int, base: int, db: str, *,
                             client_seeds: List[int],
                             broker_seed_base: int,
                             mesh_config: Optional[MeshGroupConfig] = None,
                             directory_refresh_s: float = 0.3,
                             collective_timeout_s: float = 20.0,
                             ) -> TwoHostNode:
    """Build this process's half of the deployment and authenticate its
    client. Port layout (relative to ``base``): marshal at base+1+rank,
    broker public/private at base+10+10*rank / +1."""
    mesh = pod_broker_mesh(N_SHARDS)
    my_shard = local_shard_indices(mesh)[0]

    rd = testing_run_def(broker_protocol=Tcp, user_protocol=Tcp)
    group = MultiHostBrokerGroup(
        mesh,
        mesh_config or MeshGroupConfig(
            num_user_slots=64, ring_slots=8, frame_bytes=1024,
            extra_lanes=(), direct_bucket_slots=4, batch_window_s=0.05),
        discovery=await Embedded.new(db),
        directory_refresh_s=directory_refresh_s,
        collective_timeout_s=collective_timeout_s)

    broker_pub = base + 10 + 10 * rank
    ident = BrokerIdentifier(f"127.0.0.1:{broker_pub}",
                             f"127.0.0.1:{broker_pub + 1}")
    broker = await Broker.new(BrokerConfig(
        run_def=rd,
        keypair=DEFAULT_SCHEME.generate_keypair(
            seed=broker_seed_base + rank),
        discovery_endpoint=db,
        public_advertise_endpoint=ident.public_advertise_endpoint,
        public_bind_endpoint=f"127.0.0.1:{broker_pub}",
        private_advertise_endpoint=ident.private_advertise_endpoint,
        private_bind_endpoint=f"127.0.0.1:{broker_pub + 1}",
        heartbeat_interval_s=0.5, sync_interval_s=3600,
        whitelist_interval_s=3600, form_mesh=False))
    group.attach(broker, my_shard)
    await broker.start()

    marshal_port = base + 1 + rank
    marshal = await Marshal.new(MarshalConfig(
        run_def=rd, discovery_endpoint=db,
        bind_endpoint=f"127.0.0.1:{marshal_port}"))
    await marshal.start()

    # pin placement: THIS host's marshal always assigns THIS host's
    # broker (production load-balances; the harness needs the
    # cross-host topology)
    async def pinned():
        return ident
    marshal.discovery.get_with_least_connections = pinned

    client = Client(ClientConfig(
        marshal_endpoint=f"127.0.0.1:{marshal_port}",
        keypair=DEFAULT_SCHEME.generate_keypair(seed=client_seeds[rank]),
        protocol=Tcp, subscribed_topics={0}))
    await client.ensure_initialized()
    for _ in range(100):
        if broker.connections.num_users == 1:
            break
        await asyncio.sleep(0.05)
    assert broker.connections.num_users == 1

    return TwoHostNode(rank=rank, my_shard=my_shard, ident=ident,
                       group=group, broker=broker, marshal=marshal,
                       client=client)


def spawn_worker_pair(worker_path: str, extra_args: List[str],
                      cwd: Optional[str] = None, pipe: bool = True,
                      log_dir: Optional[str] = None):
    """Parent-side harness: pick a free block of ports, spawn the two
    ranked worker processes with a jax-clean env, and return
    ``(procs, base_port)``. Callers own communicate()/asserts.
    ``log_dir`` redirects each worker to ``rank<N>.log`` there instead
    of a pipe (full output survives even when a worker is killed)."""
    base = free_port_block()
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    procs = []
    for rank in (0, 1):
        logf = None
        if log_dir is not None:
            logf = open(os.path.join(log_dir, f"rank{rank}.log"), "w")
            out = logf
        elif pipe:
            out = subprocess.PIPE
        else:
            out = None
        procs.append(subprocess.Popen(
            [sys.executable, worker_path, str(rank), str(base),
             *extra_args],
            env=env, cwd=cwd, stdout=out,
            stderr=subprocess.STDOUT if out is not None else None,
            text=True))
        if logf is not None:
            logf.close()  # the child holds its own fd now
    return procs, base
