#!/usr/bin/env python
"""Reference-shaped microbenches on the host stack (criterion parity).

Reproduces the shapes of the reference's criterion harnesses, so the repo
carries its own measured numbers (the reference publishes none):

- transport transfer throughput at 100 B / 1 KB / 100 KB / 10 MB / 100 MB
  frames over Memory and TCP-loopback (cdn-proto/benches/protocols.rs:103-159)
- broker routing latency on the deterministic injection harness: broadcast
  user→2 users and user→2 brokers; direct user→self / user→user /
  user→remote-broker / broker→user, 10 KB messages
  (cdn-broker/benches/broadcast.rs:52-110, benches/direct.rs:79-187)
- end-to-end direct-message echo p50/p99 through marshal+broker+client
  (the BASELINE.json p99 metric's host-side baseline)

Usage: python benches/host_bench.py [--quick] [--profile]
Prints one JSON object per bench line; --profile writes a cProfile dump
next to this file (the reference wires pprof flamegraphs into criterion).
"""

from __future__ import annotations

import argparse
import asyncio
import cProfile
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pushcdn_tpu.broker.test_harness import TestDefinition
from pushcdn_tpu.client import Client, ClientConfig
from pushcdn_tpu.marshal import Marshal, MarshalConfig
from pushcdn_tpu.broker.broker import Broker, BrokerConfig
from pushcdn_tpu.broker.tasks.heartbeat import heartbeat_once
from pushcdn_tpu.proto.crypto.signature import DEFAULT_SCHEME
from pushcdn_tpu.proto.def_ import testing_run_def
from pushcdn_tpu.proto.message import Broadcast, Direct
from pushcdn_tpu.proto.transport import Memory, Quic, Tcp, TcpTls
from pushcdn_tpu.proto.transport.memory import gen_testing_connection_pair

RESULTS: list[dict] = []


def emit(name: str, value: float, unit: str, **extra) -> None:
    row = {"bench": name, "value": round(value, 3), "unit": unit, **extra}
    RESULTS.append(row)
    print(json.dumps(row), flush=True)


# ---------------------------------------------------------------------------
# transport throughput (parity protocols.rs)
# ---------------------------------------------------------------------------

async def bench_transport(proto, endpoint: str, size: int, total_bytes: int,
                          **extra):
    listener = await proto.bind(endpoint)
    ep = endpoint
    port = getattr(listener, "bound_port", None)
    if port:
        ep = f"127.0.0.1:{port}"
    connect = asyncio.create_task(proto.connect(ep))
    server = await (await listener.accept()).finalize()
    client = await connect

    payload = os.urandom(size)
    msg = Direct(recipient=b"", message=payload)
    n = max(1, total_bytes // max(size, 1))

    async def sender():
        for _ in range(n):
            await client.send_message(msg)

    t0 = time.perf_counter()
    send_task = asyncio.create_task(sender())
    for _ in range(n):
        raw = await server.recv_raw()
        raw.release()
    await send_task
    dt = time.perf_counter() - t0
    client.close()
    server.close()
    await listener.close()
    emit(f"transport/{proto.name}/transfer", n * size / dt / 1e6, "MB/s",
         frame_size=size, frames=n, **extra)


# ---------------------------------------------------------------------------
# broker routing latency (parity broadcast.rs / direct.rs, 10 KB)
# ---------------------------------------------------------------------------

async def _routing_case(run, send_entity, message, recv_entities, iters: int):
    lat = []
    for _ in range(iters):
        t0 = time.perf_counter()
        await run.send_message_as(send_entity, message)
        for e in recv_entities:
            raw = await asyncio.wait_for(e.remote.recv_raw(), 5)
            raw.release()
        lat.append((time.perf_counter() - t0) * 1e6)
    return lat


async def bench_routing(iters: int):
    payload = os.urandom(10 * 1024)  # 10 KB parity

    # broadcast user -> 2 subscribed users
    run = await TestDefinition(connected_users=[[0], [0], [0]]).run()
    try:
        lat = await _routing_case(
            run, run.user(0), Broadcast(topics=[0], message=payload),
            [run.user(1), run.user(2)], iters)
        emit("routing/broadcast/user_to_2_users",
             statistics.median(lat), "us_median", p99=_p99(lat))
    finally:
        await run.shutdown()

    # broadcast user -> 2 subscribed brokers
    run = await TestDefinition(connected_users=[[0]],
                               connected_brokers=[([0], []), ([0], [])]).run()
    try:
        lat = await _routing_case(
            run, run.user(0), Broadcast(topics=[0], message=payload),
            [run.peer(0), run.peer(1)], iters)
        emit("routing/broadcast/user_to_2_brokers",
             statistics.median(lat), "us_median", p99=_p99(lat))
    finally:
        await run.shutdown()

    # direct user -> self
    run = await TestDefinition(connected_users=[[0]]).run()
    try:
        lat = await _routing_case(
            run, run.user(0), Direct(recipient=b"user-0", message=payload),
            [run.user(0)], iters)
        emit("routing/direct/user_to_self",
             statistics.median(lat), "us_median", p99=_p99(lat))
    finally:
        await run.shutdown()

    # direct user -> other user (same broker)
    run = await TestDefinition(connected_users=[[0], [0]]).run()
    try:
        lat = await _routing_case(
            run, run.user(0), Direct(recipient=b"user-1", message=payload),
            [run.user(1)], iters)
        emit("routing/direct/user_to_user",
             statistics.median(lat), "us_median", p99=_p99(lat))
    finally:
        await run.shutdown()

    # direct user -> user owned by a remote broker (one forward hop)
    run = await TestDefinition(connected_users=[[0]],
                               connected_brokers=[([], [b"remote-user"])]).run()
    try:
        lat = await _routing_case(
            run, run.user(0), Direct(recipient=b"remote-user", message=payload),
            [run.peer(0)], iters)
        emit("routing/direct/user_to_remote_broker",
             statistics.median(lat), "us_median", p99=_p99(lat))
    finally:
        await run.shutdown()

    # direct broker -> local user
    run = await TestDefinition(connected_users=[[0]],
                               connected_brokers=[([], [])]).run()
    try:
        lat = await _routing_case(
            run, run.peer(0), Direct(recipient=b"user-0", message=payload),
            [run.user(0)], iters)
        emit("routing/direct/broker_to_user",
             statistics.median(lat), "us_median", p99=_p99(lat))
    finally:
        await run.shutdown()


# ---------------------------------------------------------------------------
# end-to-end echo latency (marshal + broker + client; the p99 baseline)
# ---------------------------------------------------------------------------

async def bench_e2e_echo(iters: int):
    db = os.path.join(tempfile.mkdtemp(prefix="pushcdn-bench-"), "d.sqlite")
    rd = testing_run_def()
    broker = await Broker.new(BrokerConfig(
        run_def=rd, keypair=DEFAULT_SCHEME.generate_keypair(seed=1),
        discovery_endpoint=db,
        public_advertise_endpoint="bench-pub", public_bind_endpoint="bench-pub",
        private_advertise_endpoint="bench-priv", private_bind_endpoint="bench-priv",
        heartbeat_interval_s=3600, sync_interval_s=3600,
        whitelist_interval_s=3600))
    await broker.start()
    await heartbeat_once(broker)
    marshal = await Marshal.new(MarshalConfig(
        run_def=rd, discovery_endpoint=db, bind_endpoint="bench-marshal"))
    await marshal.start()
    client = Client(ClientConfig(
        marshal_endpoint="bench-marshal",
        keypair=DEFAULT_SCHEME.generate_keypair(seed=2),
        protocol=Memory, subscribed_topics={0}))
    await client.ensure_initialized()

    payload = os.urandom(10 * 1024)
    lat = []
    for _ in range(iters):
        t0 = time.perf_counter()
        await client.send_direct_message(client.public_key, payload)
        await client.receive_message()
        lat.append((time.perf_counter() - t0) * 1e6)
    emit("e2e/direct_echo_10KB", statistics.median(lat), "us_median",
         p50=round(statistics.median(lat), 1), p99=_p99(lat))
    client.close()
    await marshal.stop()
    await broker.stop()


async def bench_device_echo(iters: int):
    """Device-plane direct-echo latency, both policies: with the depth-1
    idle bypass (the default — sparse
    traffic host-routes, so the device plane costs the latency regime
    nothing) and with the bypass disabled (the raw staged step path, the
    floor a device-routed message pays)."""
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from pushcdn_tpu.testing import Cluster

    for label, bypass in (("bypass", 2), ("staged", 0)):
        cluster = await Cluster(num_brokers=1,
                                device_plane=DevicePlaneConfig(
                                    ring_slots=64, frame_bytes=16384,
                                    extra_lanes=(),
                                    bypass_max_items=bypass)).start()
        try:
            client = cluster.client(seed=77, topics=[0])
            await client.ensure_initialized()
            payload = os.urandom(10 * 1024)
            # warm the path (first step compiles nothing further; warmup
            # ran at broker start, but prime caches anyway)
            for _ in range(5):
                await client.send_direct_message(client.public_key, payload)
                await client.receive_message()
            lat = []
            for _ in range(iters):
                t0 = time.perf_counter()
                await client.send_direct_message(client.public_key, payload)
                await client.receive_message()
                lat.append((time.perf_counter() - t0) * 1e6)
            emit(f"e2e/device_echo_10KB_{label}", statistics.median(lat),
                 "us_median", p99=_p99(lat),
                 steps=cluster.brokers[0].device_plane.steps)
            client.close()
        finally:
            await cluster.stop()


async def bench_device_fanout(tput: int):
    """Sustained broadcast fan-out THROUGH the attached device plane, end
    to end: marshal-auth'd clients publish, frames stage into the ring,
    the routing step runs on whatever backend JAX initialised (named in
    the row), the native engine egresses per-user wire streams, and all
    16 clients fully decode. (The 8-shard mesh rows need 8 devices and
    therefore run on the virtual CPU mesh.)"""
    import jax

    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from pushcdn_tpu.testing import Cluster

    cluster = await Cluster(num_brokers=1,
                            device_plane=DevicePlaneConfig(
                                ring_slots=1024, frame_bytes=2048)).start()
    try:
        clients = [cluster.client(seed=700 + i, topics=[0])
                   for i in range(16)]
        for c in clients:
            await c.ensure_initialized()
        payload = os.urandom(1024)

        async def drain(c, n):
            got = 0
            while got < n:
                got += len(await c.receive_messages())

        # warmup: fill step-shape caches / device buffers
        drains = [asyncio.create_task(drain(c, 400)) for c in clients]
        for _ in range(200):
            await clients[0].send_broadcast_message([0], payload)
            await clients[1].send_broadcast_message([0], payload)
        await asyncio.gather(*drains)

        plane = cluster.brokers[0].device_plane
        sent = tput // 2 * 2  # two publishers: drains must match exactly
        steps0 = plane.steps
        t0 = time.perf_counter()
        drains = [asyncio.create_task(drain(c, sent)) for c in clients]
        for _ in range(sent // 2):
            await clients[0].send_broadcast_message([0], payload)
            await clients[1].send_broadcast_message([0], payload)
        await asyncio.gather(*drains)
        dt = time.perf_counter() - t0
        emit("e2e/device_plane_fanout", sent * 16 / dt, "deliveries/s",
             backend=jax.default_backend(), msgs=sent, frame=1024,
             steps=plane.steps - steps0)
        for c in clients:
            c.close()
    finally:
        await cluster.stop()


def _p99(lat):
    return round(sorted(lat)[max(0, int(len(lat) * 0.99) - 1)], 1)


async def amain(quick: bool):
    sizes = [100, 1024, 100 * 1024, 10 * 1024 * 1024]
    if not quick:
        sizes.append(100 * 1024 * 1024)
    budget = 20 * 1024 * 1024 if quick else 200 * 1024 * 1024
    floor = 1 * 1024 * 1024 if quick else 8 * 1024 * 1024  # enough frames
    # Memory rows run twice: at the reference's 8 KiB duplex window
    # (test-infra parity) and at a production-class 256 KiB window — the
    # parity constant caps large-frame rows at the pipe, not the stack
    for label, window in (("8KiB-parity", None), ("256KiB", 256 * 1024)):
        prev = Memory.set_duplex_window(window) if window else None
        try:
            for size in sizes:
                await bench_transport(Memory,
                                      f"bench-mem-{label}-{size}", size,
                                      min(budget, max(10 * size, floor)),
                                      window=label)
        finally:
            if prev is not None:
                Memory.set_duplex_window(prev)
    for size in sizes:
        await bench_transport(Tcp, "127.0.0.1:0", size,
                              min(budget, max(10 * size, floor)))
    for size in sizes:
        # kernel TCP + TLS: the apples-to-apples baseline for the
        # QUIC-class rows below (those carry TLS 1.3 too; plain TCP does
        # not, so its rows measure an unencrypted stack)
        await bench_transport(TcpTls, "127.0.0.1:0", size,
                              min(budget, max(10 * size, floor)))
    for size in sizes:
        # QUIC-class UDP: same byte budget as TCP — with congestion
        # control the flow needs the full run to leave slow start, and a
        # shorter budget would measure the ramp, not the transport
        await bench_transport(Quic, "127.0.0.1:0", size,
                              min(budget, max(10 * size, floor)))
    await bench_routing(iters=100 if quick else 500)
    await bench_e2e_echo(iters=200 if quick else 1000)
    await bench_device_echo(iters=100 if quick else 300)
    # wide memory window: models the production TCP edge (same
    # rationale as the configs benches) so the 16-way drain isn't
    # pinched at 8 KiB
    prev = Memory.set_duplex_window(256 * 1024)
    try:
        await bench_device_fanout(tput=1500 if quick else 6000)
    finally:
        Memory.set_duplex_window(prev)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--profile", action="store_true",
                    help="write host_bench.prof (pprof-flamegraph parity)")
    args = ap.parse_args()
    if args.profile:
        prof = cProfile.Profile()
        prof.enable()
    asyncio.run(amain(args.quick))
    if args.profile:
        prof.disable()
        out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "host_bench.prof")
        prof.dump_stats(out)
        print(f"# profile written to {out} (view: python -m pstats)",
              file=sys.stderr)


if __name__ == "__main__":
    main()
