"""Broker binary (parity cdn-broker/src/binaries/broker.rs:21-131).

    python -m pushcdn_tpu.bin.broker \
        --discovery-endpoint /tmp/cdn.sqlite \
        --public-advertise-endpoint local_ip:1738 --public-bind-endpoint 0.0.0.0:1738 \
        --private-advertise-endpoint local_ip:1739 --private-bind-endpoint 0.0.0.0:1739
"""

from __future__ import annotations

import argparse
import asyncio
import os

from pushcdn_tpu.bin.common import (
    add_io_impl_flag,
    add_pump_flag,
    apply_io_impl,
    apply_pump,
    drain_grace_s,
    init_logging,
    install_drain_signals,
    keypair_from_seed,
    raise_nofile_limit,
    run_def_from_args,
    tune_gc,
)
from pushcdn_tpu.broker.broker import GIB, Broker, BrokerConfig


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="pushcdn-broker", description=__doc__)
    p.add_argument("--discovery-endpoint", required=True,
                   help="sqlite path or redis:// URL")
    p.add_argument("--public-advertise-endpoint", default="local_ip:1738")
    p.add_argument("--public-bind-endpoint", default="0.0.0.0:1738")
    p.add_argument("--private-advertise-endpoint", default="local_ip:1739")
    p.add_argument("--private-bind-endpoint", default="0.0.0.0:1739")
    p.add_argument("--metrics-bind-endpoint", default=None)
    p.add_argument("--broker-transport", default="tcp")
    p.add_argument("--user-transport", default="tcp+tls")
    p.add_argument("--num-topics", type=int, default=256)
    p.add_argument("--key-seed", type=int, default=0,
                   help="deployment broker key seed (all brokers must match)")
    p.add_argument("--ca-cert-path", default=None)
    p.add_argument("--ca-key-path", default=None)
    p.add_argument("--global-memory-pool-size", type=int, default=GIB,
                   help="bytes (default 1 GiB, parity broker.rs:67-72)")
    p.add_argument("--global-permits", action="store_true")
    p.add_argument("--scheme", default="ed25519",
                   help="signature scheme: ed25519 | bls-bn254")
    p.add_argument("--heartbeat-interval", type=float, default=10.0,
                   help="discovery heartbeat cadence in seconds; chaos "
                        "drills shrink it so a killed broker ages out of "
                        "placement quickly")
    p.add_argument("--membership-ttl", type=float, default=60.0,
                   help="discovery membership TTL in seconds (parity "
                        "heartbeat.rs 60 s)")
    p.add_argument("--sync-interval", type=float, default=10.0,
                   help="mesh anti-entropy cadence in seconds (partial "
                        "user/topic syncs + LedgerSync balance sheets); "
                        "audit drills shrink it so conservation sheets "
                        "propagate quickly")
    # ---- sharded data plane (ISSUE 6) ---------------------------------
    p.add_argument("--shards", type=int, default=None,
                   help="shard the data plane across N worker OS "
                        "processes (default: PUSHCDN_SHARDS or 1 = "
                        "single-process, byte-for-byte today's behavior)."
                        " Shard 0 owns the mesh; users spread across "
                        "workers via SO_REUSEPORT (or parent fd-handoff)")
    p.add_argument("--shard-index", type=int, default=None,
                   help=argparse.SUPPRESS)  # internal: worker role
    p.add_argument("--shard-ipc", default=None,
                   help=argparse.SUPPRESS)  # internal: worker IPC spec
    # ---- device data plane (the TPU path) -----------------------------
    p.add_argument("--device-plane", action="store_true",
                   help="route eligible messages through the attached "
                        "device (single-shard plane; see --multihost for "
                        "the cross-host mesh group)")
    p.add_argument("--device-ring-slots", type=int, default=None,
                   help="staging ring slots per step (defaults: 1024 "
                        "single-shard, 256 mesh-group)")
    p.add_argument("--device-frame-bytes", type=int, default=None,
                   help="frame slot bytes (default 2048)")
    p.add_argument("--device-batch-window", type=float, default=None,
                   help="seconds. Single-shard: the coalescing window for "
                        "trickle traffic (bursts and idle arrivals skip "
                        "it; default 1 ms). Mesh group: the LOCKSTEP step "
                        "cadence every host ticks at (default 1 ms)")
    # ---- multi-host SPMD mesh group (jax.distributed) -----------------
    p.add_argument("--multihost-coordinator", default=None,
                   help="host:port of the jax.distributed coordinator; "
                        "enables the cross-host mesh broker group "
                        "(auto-detected on Cloud TPU if flags are "
                        "omitted but --mesh-shards is given)")
    p.add_argument("--multihost-process-id", type=int, default=None)
    p.add_argument("--multihost-num-processes", type=int, default=None)
    p.add_argument("--mesh-shards", type=int, default=None,
                   help="global broker-mesh shard count; this broker "
                        "attaches to --mesh-shard (default: first local)")
    p.add_argument("--mesh-shard", type=int, default=None)
    add_io_impl_flag(p)
    add_pump_flag(p)
    p.add_argument("-v", "--verbose", action="count", default=0)
    return p


def _worker_argv_base() -> list:
    """This process's argv minus the flags the supervisor rewrites per
    worker (--shards; --metrics-bind-endpoint is reassigned per shard)."""
    import sys
    argv = []
    skip = False
    for a in sys.argv[1:]:
        if skip:
            skip = False
            continue
        if a in ("--shards", "--metrics-bind-endpoint"):
            skip = True
            continue
        if a.startswith("--shards=") or \
                a.startswith("--metrics-bind-endpoint="):
            continue
        argv.append(a)
    return argv


async def run_supervisor(args: argparse.Namespace, shards: int) -> None:
    """Parent of a sharded broker: spawn N workers, relay control-plane
    deltas, aggregate observability, propagate drains (ISSUE 6)."""
    import sys

    from pushcdn_tpu.broker import sharding

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    os.environ["PYTHONPATH"] = (
        repo + os.pathsep + os.environ["PYTHONPATH"]
        if os.environ.get("PYTHONPATH") else repo)
    base = _worker_argv_base()

    def worker_argv(shard: int, spec_json: str, metrics_endpoint):
        argv = [sys.executable, "-m", "pushcdn_tpu.bin.broker", *base,
                "--shard-index", str(shard), "--shard-ipc", spec_json]
        if metrics_endpoint:
            argv += ["--metrics-bind-endpoint", metrics_endpoint]
        return argv

    acceptor = None
    if not sharding.reuseport_available():
        if args.user_transport != "tcp":
            # the handoff acceptor deals RAW TCP fds; a TLS/QUIC user
            # transport would silently answer handshakes in plaintext
            # (or never accept at all) — refuse loudly instead
            raise SystemExit(
                "--shards without SO_REUSEPORT uses the parent fd-handoff "
                "acceptor, which supports only --user-transport tcp "
                f"(got {args.user_transport!r}); use a platform with "
                "SO_REUSEPORT for TLS/QUIC user transports")
        acceptor = args.public_bind_endpoint
    sup = sharding.ShardSupervisor(
        shards, args.metrics_bind_endpoint, worker_argv,
        acceptor_endpoint=acceptor)
    try:
        await sup.start()
    except BaseException:
        # half-started (e.g. parent metrics bind EADDRINUSE after the
        # workers spawned): kill whatever came up and unlink the rings —
        # REUSEPORT workers would otherwise keep serving as orphans
        sup.signal_workers()
        await sup.reap(5.0)
        await sup.stop()
        raise
    drain = asyncio.Event()
    installed = install_drain_signals(drain, on_signal=sup.begin_drain)
    exit_task = asyncio.create_task(sup.wait_any_worker_exit(),
                                    name="shard-reaper")
    drain_task = asyncio.create_task(drain.wait(), name="drain-wait")
    try:
        await asyncio.wait({exit_task, drain_task},
                           return_when=asyncio.FIRST_COMPLETED)
        if installed and drain.is_set():
            # workers flipped not-ready on the forwarded SIGTERM and are
            # serving out the grace window; reap them BEFORE the parent's
            # aggregated endpoint goes away
            await sup.reap(drain_grace_s() + 15.0)
            await sup.stop()
            return
        rc = exit_task.result() if exit_task.done() else 1
        sup.signal_workers()
        await sup.reap(5.0)
        await sup.stop()
        raise SystemExit(rc if rc not in (0, None) else 1)
    finally:
        for t in (exit_task, drain_task):
            t.cancel()


async def amain(args: argparse.Namespace) -> None:
    from pushcdn_tpu.broker import sharding

    shards = sharding.shards_from_env(args.shards)
    if shards > 1 and (args.device_plane or args.mesh_shards is not None):
        raise SystemExit("--shards is a host-data-plane feature; combine "
                         "with --device-plane/--mesh-shards once the "
                         "device plane learns shard-local staging")
    if args.shard_index is None and shards > 1:
        await run_supervisor(args, shards)
        return

    run_def = run_def_from_args(args.broker_transport, args.user_transport,
                                args.discovery_endpoint, args.num_topics,
                                args.global_permits, scheme=args.scheme)
    if args.device_plane and args.mesh_shards is not None:
        raise SystemExit("--device-plane (single-shard) and --mesh-shards "
                         "(mesh group) are mutually exclusive")
    if args.mesh_shard is not None and args.mesh_shards is None:
        raise SystemExit("--mesh-shard requires --mesh-shards")
    def _overrides():
        out = {}
        if args.device_ring_slots is not None:
            out["ring_slots"] = args.device_ring_slots
        if args.device_frame_bytes is not None:
            out["frame_bytes"] = args.device_frame_bytes
        if args.device_batch_window is not None:
            out["batch_window_s"] = args.device_batch_window
        return out

    spec = None
    if args.shard_index is not None:
        import json as json_mod
        if not args.shard_ipc:
            raise SystemExit("--shard-index is internal (spawned by "
                             "--shards); it requires --shard-ipc")
        spec = json_mod.loads(args.shard_ipc)
        # per-worker span log: the workers inherit the parent's
        # PUSHCDN_TRACE_LOG — suffix it so two shards never interleave
        # writes in one JSONL (proto.trace reads the env at import, but
        # lazily opens the file, so adjusting here is race-free)
        trace_path = os.environ.get("PUSHCDN_TRACE_LOG")
        if trace_path:
            from pushcdn_tpu.proto import trace as trace_mod_
            root, ext = os.path.splitext(trace_path)
            trace_mod_._LOG_PATH = f"{root}-shard{spec['shard']}{ext}"

    device_plane = None
    device_runtime = None
    if args.device_plane:
        # before any array: place the compile cache, and refuse a CPU
        # backend nobody asked for — a broker told to use a device never
        # serves from the CPU JAX falls back to on its own
        from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
        from pushcdn_tpu.parallel import runtime
        device_runtime = runtime.init("broker --device-plane")
        device_plane = DevicePlaneConfig(**_overrides())
    broker = await Broker.new(BrokerConfig(
        run_def=run_def,
        keypair=keypair_from_seed(args.key_seed, args.scheme),
        discovery_endpoint=args.discovery_endpoint,
        public_advertise_endpoint=args.public_advertise_endpoint,
        public_bind_endpoint=args.public_bind_endpoint,
        private_advertise_endpoint=args.private_advertise_endpoint,
        private_bind_endpoint=args.private_bind_endpoint,
        metrics_bind_endpoint=args.metrics_bind_endpoint,
        ca_cert_path=args.ca_cert_path, ca_key_path=args.ca_key_path,
        global_memory_pool_size=args.global_memory_pool_size,
        heartbeat_interval_s=args.heartbeat_interval,
        membership_ttl_s=args.membership_ttl,
        sync_interval_s=args.sync_interval,
        device_plane=device_plane,
        # a mesh-group deployment's inter-broker plane is the device mesh
        form_mesh=args.mesh_shards is None,
        # worker-shard role (ISSUE 6): shard 0 owns mesh + control tasks
        shard_index=(spec["shard"] if spec else 0),
        num_shards=(spec["num_shards"] if spec else 1),
        bind_private=(spec is None or spec["shard"] == 0),
        reuse_port=(spec is not None and "accept_fd" not in spec),
        accept_handoff_fd=(spec.get("accept_fd") if spec else None),
    ))
    broker.device_runtime = device_runtime
    if spec is not None:
        from pushcdn_tpu.broker import sharding
        sharding.runtime_from_spec(broker, spec).attach()
    if args.mesh_shards is not None:
        # cross-host SPMD mesh group: join the distributed runtime, build
        # the global mesh, attach this broker to its shard
        from pushcdn_tpu.broker.mesh_group import MeshGroupConfig
        from pushcdn_tpu.broker.multihost_group import MultiHostBrokerGroup
        from pushcdn_tpu.parallel import multihost
        multihost.initialize(args.multihost_coordinator,
                             args.multihost_num_processes,
                             args.multihost_process_id)
        # only now may the backend initialise (jax.distributed joins
        # first): same cache placement and CPU refusal as --device-plane
        from pushcdn_tpu.parallel import runtime
        broker.device_runtime = runtime.init("broker --mesh-shards")
        mesh = multihost.pod_broker_mesh(args.mesh_shards)
        group = MultiHostBrokerGroup(
            mesh, MeshGroupConfig(**_overrides()),
            discovery=broker.discovery)
        shard = (args.mesh_shard if args.mesh_shard is not None
                 else group.local_shards[0])
        if shard not in group.local_shards:
            raise SystemExit(
                f"--mesh-shard {shard} is not local to this host "
                f"(local shards: {group.local_shards}) — a non-local "
                "attachment would silently blackhole traffic")
        group.attach(broker, shard)
    # Graceful drain (ISSUE 5): SIGINT/SIGTERM flips /readyz to 503 FIRST,
    # keeps serving in-flight traffic for PUSHCDN_DRAIN_GRACE_S, then
    # stops — so a load balancer stops routing before the listeners close.
    drain = asyncio.Event()
    if not install_drain_signals(drain):
        await broker.run_until_failure()
        return
    run_task = asyncio.create_task(broker.run_until_failure(),
                                   name="broker-run")
    drain_task = asyncio.create_task(drain.wait(), name="drain-wait")
    try:
        await asyncio.wait({run_task, drain_task},
                           return_when=asyncio.FIRST_COMPLETED)
        if drain.is_set():
            broker.begin_drain("signal")
            # elastic drain (ISSUE 12): actively re-home every connected
            # user to the surviving brokers before the grace sleep — the
            # UserSync evictions land while we're still serving
            try:
                from pushcdn_tpu.broker import rehome as rehome_mod
                await rehome_mod.rehome_users(broker)
            except Exception as exc:
                import logging
                logging.getLogger("pushcdn.broker").warning(
                    "drain re-home failed: %r", exc)
            await asyncio.sleep(drain_grace_s())
            run_task.cancel()
            await asyncio.gather(run_task, return_exceptions=True)
            await broker.stop()
        else:
            await run_task  # re-raise the core-task failure
    finally:
        drain_task.cancel()


def main() -> None:
    args = build_parser().parse_args()
    init_logging(args.verbose)
    raise_nofile_limit()
    apply_io_impl(args)
    apply_pump(args)
    tune_gc()
    try:
        asyncio.run(amain(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
