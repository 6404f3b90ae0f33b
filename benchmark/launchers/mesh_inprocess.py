#!/usr/bin/env python3
"""Launcher ``mesh_inprocess``: a ``MeshBrokerGroup`` over the host's
chips, one ``Broker`` per shard with a real TCP user listener, an
in-process ``Marshal`` on TCP, SQLite discovery — all in this one
process, because the mesh group has no binary (``bin/broker
--mesh-shards`` builds the multi-process group, which one process per
chip cannot run).

A copy, in the benchmark's directory, of what
``pushcdn_tpu/testing/mesh_cluster.MeshCluster`` wires — the same
constructors, ``MeshGroupConfig`` defaults, no host broker links
(``form_mesh=False``) — with TCP (or what the configuration's
``user_transport`` and ``signature_scheme`` say) in place of the Memory
transport and the whole 256-topic space. Users are placed by steering the load figure
in discovery before each group connects, as ``MeshCluster.place_client``
does; the brokers' own heartbeat and sync are therefore parked at
3,600 s, as there.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest  # noqa: E402
from benchmark.launchers import control  # noqa: E402

PARKED_S = 3600.0
ELSEWHERE = 1_000_000  # load figure of the shards not being filled


async def amain(cfg: dict, workdir: str) -> int:
    t_spawn = time.monotonic_ns()
    from pushcdn_tpu.parallel import runtime
    rt = runtime.init("benchmark mesh launcher")
    import jax

    from pushcdn_tpu.bin.common import (
        free_ports,
        keypair_from_seed,
        run_def_from_args,
    )
    from pushcdn_tpu.broker.broker import Broker, BrokerConfig
    from pushcdn_tpu.broker.mesh_group import MeshBrokerGroup, MeshGroupConfig
    from pushcdn_tpu.marshal import Marshal, MarshalConfig
    from pushcdn_tpu.parallel.mesh import make_broker_mesh
    from pushcdn_tpu.proto.discovery.embedded import Embedded

    shards = cfg["shards"]
    if rt.device.count < shards:
        print(f"mesh launcher: {shards} shards need {shards} devices, JAX "
              f"shows {rt.device.count}", file=sys.stderr)
        return 3
    db = os.path.join(workdir, "discovery.sqlite")
    clients = manifest.client_settings(cfg)
    run_def = run_def_from_args("tcp", clients["user_transport"], db, 256,
                                scheme=clients["signature_scheme"])
    ports = free_ports(2 * shards + 1)
    mesh = make_broker_mesh(shards, devices=jax.devices()[:shards])
    group = MeshBrokerGroup(mesh, MeshGroupConfig())
    brokers = []
    for i in range(shards):
        public = f"127.0.0.1:{ports[2 * i]}"
        private = f"127.0.0.1:{ports[2 * i + 1]}"
        broker = await Broker.new(BrokerConfig(
            run_def=run_def,
            keypair=keypair_from_seed(0, clients["signature_scheme"]),
            discovery_endpoint=db,
            public_advertise_endpoint=public, public_bind_endpoint=public,
            private_advertise_endpoint=private, private_bind_endpoint=private,
            heartbeat_interval_s=PARKED_S, sync_interval_s=PARKED_S,
            whitelist_interval_s=PARKED_S, membership_ttl_s=PARKED_S,
            form_mesh=False))
        group.attach(broker, i)
        await broker.start()  # the first start warms the group up
        brokers.append(broker)
    plane_ready_ns = time.monotonic_ns()

    async def place(shard: int) -> None:
        for i, broker in enumerate(brokers):
            handle = await Embedded.new(db, identity=broker.identity)
            await handle.perform_heartbeat(
                0 if i == shard else ELSEWHERE, PARKED_S)
            await handle.close()

    await place(0)
    marshal_endpoint = f"127.0.0.1:{ports[-1]}"
    marshal = await Marshal.new(MarshalConfig(
        run_def=run_def, discovery_endpoint=db,
        bind_endpoint=marshal_endpoint))
    await marshal.start()

    loop = asyncio.get_running_loop()

    def on_loop(coro_fn):
        """Run a handler's coroutine on the event loop from the control
        thread (group and brokers are event-loop-only objects)."""
        def handler(cmd: dict) -> dict:
            future = asyncio.run_coroutine_threadsafe(coro_fn(cmd), loop)
            try:
                return future.result(timeout=control.TOPOLOGY_WAIT_S)
            except TimeoutError:
                future.cancel()
                raise control.NoAnswer(
                    f"the group's event loop: no answer to {cmd['cmd']!r} "
                    f"within {control.TOPOLOGY_WAIT_S:.0f} s") from None
        return handler

    async def do_place(cmd: dict) -> dict:
        await place(cmd["group"])
        return {"event": "placed"}

    async def do_counters(_cmd: dict) -> dict:
        return {**control.scalars(brokers[0].device_plane.describe()),
                "event": "counters", "t_ns": time.monotonic_ns(),
                "users": sum(b.connections.num_users for b in brokers),
                "users_by_shard": [b.connections.num_users for b in brokers],
                "unmirrored": len(group._unmirrored),
                "memory_peak_bytes": control.memory_peak_bytes(),
                "steps": group.steps, "frames_staged": group.frames_staged,
                "messages_routed": group.messages_routed,
                "disabled": group.disabled,
                "collectives_last_trace": group.collectives_last_trace,
                "warmup_s": (plane_ready_ns - t_spawn) / 1e9,
                **rt.compiles.snapshot()}

    control.serve({"place": on_loop(do_place),
                   "counters": on_loop(do_counters),
                   "trace": control.trace_span})
    control.emit(
        "ready", marshal=marshal_endpoint, route_pids=[os.getpid()],
        spawn_ns=t_spawn, plane_ready_ns=plane_ready_ns,
        device=dict(zip(("platform", "kind", "count"), rt.device)),
        plane={"mesh_shards": shards,
               "fused_collective": group.config.fused_collective},
        compile_cache=rt.cache_dir)

    stop = asyncio.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    await marshal.stop()
    for broker in brokers:
        await broker.stop()
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--workdir", required=True)
    args = ap.parse_args()
    with open(args.config) as f:
        cfg = json.load(f)
    from pushcdn_tpu.bin.common import init_logging, tune_gc
    init_logging(0)
    tune_gc()
    return asyncio.run(amain(cfg, args.workdir))


if __name__ == "__main__":
    sys.exit(main())
