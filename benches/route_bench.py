#!/usr/bin/env python
"""Broker routing hot-path bench: decoded broker-forwarding, scalar vs
cut-through (ISSUE 3 tentpole; the 326K msgs/s round-5 floor is the
scalar decoded-forwarding number this targets at ≥2x).

Three tiers, each one JSON line per implementation (medians of repeated
trials, all trials disclosed — the deployment core is shared, so single
samples lie):

- ``route/plan``: the decode+route+egress-build core, no wire. scalar =
  per-frame ``deserialize`` → prune → interest query → ``EgressBatch``
  clone-appends (exactly the receive loops' per-frame work); native = one
  ``route_plan`` kernel call per chunk + numpy per-peer grouping + the
  zero-copy/gather egress build. This is the kernel's honest A/B.
- ``route/forward``: end-to-end broker forwarding — a real injected
  broker (test harness, Memory transport), one sender fanning Broadcast
  chunks to N subscribed receivers, counted at the receivers' transport
  drain. Includes wire + writer + receiver cost, so the ratio is smaller
  than route/plan's.
- ``route/ratio``: native/python summary per tier.

Usage: python benches/route_bench.py [--quick] [--route-impl auto|native|python]
(--route-impl restricts which implementations run; default both.)
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import re
import statistics
import sys
import time
from typing import Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

RESULTS: list[dict] = []


def emit(name: str, value: float, unit: str, **extra) -> None:
    row = {"bench": name, "value": round(value, 1), "unit": unit, **extra}
    RESULTS.append(row)
    print(json.dumps(row), flush=True)


def _build_chunk(n_frames: int, payload: int, n_topics: int,
                 direct_every: int, seed: int = 7):
    """One FrameChunk-shaped batch: length-delimited buffer + offs/lens.
    Mostly Broadcasts across ``n_topics`` topics, every ``direct_every``-th
    frame a Direct to a known local user."""
    from pushcdn_tpu.proto.message import Broadcast, Direct, serialize
    rng = np.random.default_rng(seed)
    body = bytes(rng.integers(0, 256, payload, dtype=np.uint8))
    frames = []
    for i in range(n_frames):
        if direct_every and i % direct_every == direct_every - 1:
            frames.append(serialize(Direct(b"user-1", body)))
        else:
            frames.append(serialize(Broadcast([int(i) % n_topics], body)))
    buf = bytearray()
    offs, lens = [], []
    for f in frames:
        offs.append(len(buf) + 4)
        lens.append(len(f))
        buf += len(f).to_bytes(4, "big") + f
    return bytes(buf), offs, lens


# ---------------------------------------------------------------------------
# tier 1: decode+route+egress-build, no wire (the kernel A/B)
# ---------------------------------------------------------------------------

async def bench_plan(impls, n_users: int, n_frames: int, trials: int) -> dict:
    from pushcdn_tpu.broker.tasks import cutthrough
    from pushcdn_tpu.broker.tasks.handlers import (
        EgressBatch, route_broadcast, route_direct)
    from pushcdn_tpu.broker.tasks.senders import pre_encode_frames
    from pushcdn_tpu.broker.test_harness import TestDefinition
    from pushcdn_tpu.proto.def_ import no_hook
    from pushcdn_tpu.proto.limiter import Bytes
    from pushcdn_tpu.proto.message import Broadcast, Direct, deserialize

    # 8 subscribers on topic 0 (the fan-out set), the rest parked on the
    # other TEST topic (realistic table size, not hit by the traffic); a
    # peer broker subscribed to topic 0 and owning one remote direct user
    run = await TestDefinition(
        connected_users=[[0]] * 8 + [[1]] * (n_users - 8),
        connected_brokers=[([0], [b"remote-user"])],
    ).run()
    medians: dict = {}
    try:
        broker = run.broker
        buf, offs, lens = _build_chunk(n_frames, payload=256, n_topics=1,
                                       direct_every=8)
        results = {}

        if "python" in impls:
            hook = no_hook
            topics = broker.run_def.topics
            rates = []
            for _ in range(trials):
                t0 = time.perf_counter()
                egress = EgressBatch(broker)
                interest_cache: dict = {}
                for o, ln in zip(offs, lens):
                    raw = Bytes(buf[o:o + ln])
                    message = deserialize(raw.data)
                    if hook(b"user-0", message):
                        pass
                    if isinstance(message, Direct):
                        route_direct(broker, message.recipient, raw,
                                     to_user_only=False, egress=egress)
                    elif isinstance(message, Broadcast):
                        pruned, _bad = topics.prune(message.topics)
                        if pruned:
                            route_broadcast(broker, pruned, raw,
                                            to_users_only=False,
                                            egress=egress,
                                            interest_cache=interest_cache)
                    raw.release()
                # egress-build: the flush's per-peer pre-encode (the copy
                # the scalar path pays before the writer), wire excluded
                for frames_l in list(egress.users.values()) \
                        + list(egress.brokers.values()):
                    if len(frames_l) >= 2:
                        pre_encode_frames(frames_l)
                    for f in frames_l:
                        f.release()
                egress.users.clear()
                egress.brokers.clear()
                rates.append(n_frames / (time.perf_counter() - t0))
            results["python"] = rates

        if "native" in impls:
            planner = None
            state = cutthrough.acquire(broker, no_hook)
            if state is not None and state._refresh():
                planner = state.planner
            if planner is None:
                emit("route/plan", 0, "skipped", impl="native",
                     reason="native route-plan kernel unavailable")
            else:
                offs_np = np.asarray(offs, np.int64)
                lens_np = np.asarray(lens, np.int64)
                rates = []
                for _ in range(trials):
                    t0 = time.perf_counter()
                    pos, n = 0, len(offs)
                    built = 0
                    while pos < n:
                        consumed, stop, peers, frames = planner.plan(
                            buf, offs_np, lens_np, pos, 0)
                        # per-peer grouping + egress-build (the same numpy
                        # path _send_plan runs, minus the writer enqueue)
                        if len(peers):
                            order = np.argsort(peers, kind="stable")
                            speers = peers[order]
                            sframes = frames[order]
                            bounds = np.nonzero(np.diff(speers))[0] + 1
                            starts = np.concatenate(([0], bounds))
                            ends = np.concatenate((bounds, [len(speers)]))
                            mv = memoryview(buf)
                            for s, e in zip(starts.tolist(), ends.tolist()):
                                idx = sframes[s:e]
                                first, last = int(idx[0]), int(idx[-1])
                                if last - first + 1 == len(idx):
                                    built += len(
                                        mv[int(offs_np[first]) - 4:
                                           int(offs_np[last])
                                           + int(lens_np[last])])
                                else:
                                    built += len(planner.gather(
                                        buf, offs_np, lens_np, idx))
                        pos += consumed
                        if stop == 1:  # residual (none in this mix)
                            pos += 1
                    rates.append(n_frames / (time.perf_counter() - t0))
                results["native"] = rates

        for impl, rates in results.items():
            med = statistics.median(rates)
            medians[impl] = med
            emit("route/plan", med, "msgs/s", impl=impl,
                 frames=n_frames, users=n_users, payload=256,
                 trials=[round(r, 1) for r in rates],
                 max=round(max(rates), 1))
    finally:
        await run.shutdown()
    return medians


# ---------------------------------------------------------------------------
# tier 3: trace overhead (ISSUE 4) — same forwarding loop, every 1024th
# frame stamped with the lifecycle-trace wire flag (what a publisher at
# the default PUSHCDN_TRACE_SAMPLE=1024 produces). Budget: tracing ON
# within 2% of OFF — traced frames take the instrumented scalar path,
# the other 1023 stay on the batch plan.
# ---------------------------------------------------------------------------

async def bench_profiler_overhead(impl: str, receivers: int, msgs: int,
                                  trials: int, sample: int = 1024,
                                  rounds: int = 3) -> dict:
    """ISSUE 5 budget row: what does turning on THIS PR's additions cost?

    Baseline (``plane=off``): the PR-4 shipped state — tracing at the
    default 1/1024 sample, receivers emitting delivery spans (a real
    client decodes every frame anyway; the span emit is the marginal
    cost) which feed the new ``cdn_e2e_latency_seconds`` histogram.
    Measurement (``plane=on``): the same, plus the task-sampling profiler
    ticking at its default interval. The delta — the profiler + the e2e
    histogram's per-traced-delivery observe — must stay ≤2%.

    A/B rounds are INTERLEAVED (off/on alternating) because a shared
    deployment core drifts over a multi-second bench: back-to-back
    blocks would attribute the drift to whichever side ran last.
    Also runs a denser-sampled pass (1/64) purely to populate the e2e
    latency percentiles for BENCH_r09.json."""
    from pushcdn_tpu.proto import metrics as metrics_mod
    from pushcdn_tpu.testing.routebench import forward_rate
    out: dict = {}
    offs: list = []
    ons: list = []
    skipped = False
    for r in range(rounds):
        for plane in (("off", "on") if r % 2 == 0 else ("on", "off")):
            profiler = None
            if plane == "on":
                # explicit shipped-default interval: the A/B must profile
                # even when the operator env disabled the profiler
                profiler = asyncio.create_task(
                    metrics_mod._task_profiler(0.25))
            try:
                res = await forward_rate(impl, receivers=receivers,
                                         msgs=msgs, trials=trials,
                                         trace_every=sample,
                                         deliver_spans=True)
            finally:
                if profiler is not None:
                    profiler.cancel()
            if res is None:
                skipped = True
                break
            (ons if plane == "on" else offs).append(res["median"])
            gc.collect()
        if skipped:
            break
    if skipped or not offs or not ons:
        emit("route/profiler_overhead", 0, "skipped", impl=impl,
             reason="native route-plan kernel unavailable")
        return out
    off_med = statistics.median(offs)
    on_med = statistics.median(ons)
    emit("route/profiler_overhead", off_med, "msgs/s", impl=impl,
         plane="off", sample=sample, receivers=receivers, msgs=msgs,
         trials=[round(r, 1) for r in offs])
    emit("route/profiler_overhead", on_med, "msgs/s", impl=impl,
         plane="on", sample=sample, receivers=receivers, msgs=msgs,
         trials=[round(r, 1) for r in ons])
    if off_med:
        ratio = on_med / off_med
        # the headline ``value`` rounds to 0.1 — useless against a 2%
        # budget, so the precise delta rides the pct field
        emit("route/profiler_overhead", ratio, "x", impl=impl,
             tier="on-vs-off", pct=round((ratio - 1) * 100, 2))
        out["profiler_overhead_ratio"] = round(ratio, 4)
        out["profiler_overhead_pct"] = round((ratio - 1) * 100, 2)
        out["headline_msgs_s"] = round(on_med, 1)
    # e2e percentile source: denser sampling (stats row, not a rate row)
    e2e = await forward_rate(impl, receivers=receivers,
                             msgs=max(msgs // 2, 1000), trials=1,
                             trace_every=64, deliver_spans=True)
    lats = sorted((e2e or {}).get("e2e_lat_s") or [])
    if lats:
        def pct(q):
            return lats[min(int(q * len(lats)), len(lats) - 1)]
        out["e2e_p50_ms"] = round(pct(0.50) * 1e3, 3)
        out["e2e_p99_ms"] = round(pct(0.99) * 1e3, 3)
        emit("route/e2e_latency", out["e2e_p50_ms"], "ms", impl=impl,
             tier="p50", samples=len(lats))
        emit("route/e2e_latency", out["e2e_p99_ms"], "ms", impl=impl,
             tier="p99", samples=len(lats))
    return out


async def bench_trace_overhead(impl: str, receivers: int, msgs: int,
                               trials: int, sample: int = 1024) -> None:
    from pushcdn_tpu.testing.routebench import forward_rate
    off = await forward_rate(impl, receivers=receivers, msgs=msgs,
                             trials=trials)
    on = await forward_rate(impl, receivers=receivers, msgs=msgs,
                            trials=trials, trace_every=sample)
    if off is None or on is None:
        emit("route/trace_overhead", 0, "skipped", impl=impl,
             reason="native route-plan kernel unavailable")
        return
    emit("route/trace_overhead", off["median"], "msgs/s", impl=impl,
         trace="off", receivers=receivers, msgs=off["msgs"],
         trials=[round(r, 1) for r in off["trials"]])
    emit("route/trace_overhead", on["median"], "msgs/s", impl=impl,
         trace="on", sample=sample, receivers=receivers, msgs=on["msgs"],
         trials=[round(r, 1) for r in on["trials"]])
    if off["median"]:
        emit("route/trace_overhead", on["median"] / off["median"], "x",
             impl=impl, tier="on-vs-off")


# ---------------------------------------------------------------------------
# tier 4 (ISSUE 6): multi-core shard scaling — REAL OS processes over TCP
# ---------------------------------------------------------------------------

def _free_port_block() -> int:
    import socket
    while True:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        if port <= 64000:
            return port


async def _shard_forward_once(shards: int, receivers: int, msgs: int,
                              trials: int, payload: int,
                              batch: int = 64) -> Optional[dict]:
    """One shard-count row: spawn discovery + marshal + ONE broker binary
    (``--shards N``) as real processes, drive 1 sender + R receivers via
    the real client library over TCP, count at the receivers' transport
    drain. ``--shards 1`` is the same-run baseline (byte-for-byte the
    single-process broker)."""
    import signal
    import tempfile

    from pushcdn_tpu.bin.common import keypair_from_seed, spawn_binary
    from pushcdn_tpu.client import Client, ClientConfig
    from pushcdn_tpu.proto.message import Broadcast, serialize
    from pushcdn_tpu.proto.transport.base import FrameChunk
    from pushcdn_tpu.proto.transport.tcp import Tcp

    bp = _free_port_block()
    db = os.path.join(tempfile.mkdtemp(prefix="pushcdn-shardbench-"),
                      "cdn.sqlite")
    procs = []
    clients = []
    try:
        procs.append(spawn_binary(
            "broker",
            "--discovery-endpoint", db,
            "--public-advertise-endpoint", f"127.0.0.1:{bp}",
            "--public-bind-endpoint", f"127.0.0.1:{bp}",
            "--private-advertise-endpoint", f"127.0.0.1:{bp + 1}",
            "--private-bind-endpoint", f"127.0.0.1:{bp + 1}",
            "--user-transport", "tcp", "--broker-transport", "tcp",
            "--shards", str(shards),
            # deterministic round-robin accept spread: receiver i lands on
            # worker i % N (SO_REUSEPORT's hash spread is luck-dependent
            # at 9 connections; the measured data path is identical).
            # capture=False: the bench never drains the pipe, and a
            # blocked log write would wedge the measured processes.
            env_extra={"PUSHCDN_SHARD_ACCEPT": "handoff"}, capture=False))
        procs.append(spawn_binary(
            "marshal",
            "--discovery-endpoint", db,
            "--bind-endpoint", f"127.0.0.1:{bp + 2}",
            "--user-transport", "tcp", capture=False))
        await asyncio.sleep(1.0)

        async def connect(seed: int, topics) -> Client:
            c = Client(ClientConfig(
                marshal_endpoint=f"127.0.0.1:{bp + 2}",
                keypair=keypair_from_seed(seed),
                protocol=Tcp, subscribed_topics=set(topics)))
            async with asyncio.timeout(30):
                while True:
                    try:
                        await c.ensure_initialized()
                        return c
                    except Exception:
                        await asyncio.sleep(0.3)

        for r in range(receivers):
            clients.append(await connect(100 + r, [0]))
        sender = await connect(99, [])
        clients.append(sender)
        await asyncio.sleep(0.7)  # interest deltas settle across shards

        frame = serialize(Broadcast([0], os.urandom(payload)))
        msgs = max(batch, (msgs // batch) * batch)

        async def drain(conn, n):
            got = 0
            async with asyncio.timeout(180):
                while got < n:
                    for item in await conn.recv_frames(n - got):
                        got += item.remaining if type(item) is FrameChunk \
                            else 1
                        item.release()

        rates = []
        for _ in range(trials):
            t0 = time.perf_counter()
            drains = [asyncio.create_task(
                drain(clients[r]._connection, msgs))
                for r in range(receivers)]
            send_conn = sender._connection
            for _ in range(msgs // batch):
                await send_conn.send_raw_many([frame] * batch)
                await asyncio.sleep(0)
            await asyncio.gather(*drains)
            rates.append(msgs / (time.perf_counter() - t0))
        med = statistics.median(rates)
        return {"median": med, "trials": rates, "msgs": msgs,
                "delivered": med * receivers}
    except (asyncio.TimeoutError, Exception) as exc:
        emit("route/shard_forward", 0, "skipped", shards=shards,
             reason=f"harness failed: {exc!r}")
        return None
    finally:
        for c in clients:
            try:
                c.close()
            except Exception:
                pass
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGINT)
        deadline = time.time() + 8.0
        while time.time() < deadline and any(p.poll() is None
                                             for p in procs):
            await asyncio.sleep(0.1)
        for p in procs:
            if p.poll() is None:
                p.kill()


async def bench_shard_scaling(shard_counts, receivers: int, msgs: int,
                              trials: int, payload: int = 512) -> dict:
    """Shard-count rows (1/2/4) for the 8-receiver forwarding figure.
    Labels carry the host's usable core count — on a 1-core container the
    rows are honestly flat; near-linear scaling needs cores >= shards."""
    cpus = len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity") \
        else (os.cpu_count() or 1)
    out: dict = {}
    for n in shard_counts:
        res = await _shard_forward_once(n, receivers, msgs, trials, payload)
        gc.collect()
        if res is None:
            continue
        out[n] = res["median"]
        emit("route/shard_forward", res["median"], "msgs/s", shards=n,
             receivers=receivers, msgs=res["msgs"], payload=payload,
             delivered_msgs_s=round(res["delivered"], 1), cpus=cpus,
             backend="cpu",
             trials=[round(r, 1) for r in res["trials"]])
    base = out.get(1)
    if base:
        for n, med in out.items():
            if n != 1:
                emit("route/shard_forward", med / base, "x",
                     tier=f"shards{n}-vs-1", cpus=cpus,
                     note=("scaling requires cores >= shards; "
                           f"this host has {cpus}"))
    return {f"shard{n}_msgs_s": round(v, 1) for n, v in out.items()}


# ---------------------------------------------------------------------------
# tier 5 (ISSUE 7): forwarding under sustained subscribe churn —
# incremental deltas vs the rebuild-guard baseline, same churn machinery
# ---------------------------------------------------------------------------

async def bench_churn_forward(receivers: int, msgs: int,
                              parked_users: int, trials: int,
                              sample: int = 64) -> dict:
    """The ISSUE 7 acceptance A/B: one broker carrying ``parked_users``
    extra subscriptions (a big interest table) forwards broadcasts while
    a churner floods Subscribe/Unsubscribe. mode=incremental applies
    typed deltas in place; mode=rebuild is the pre-ISSUE-7 baseline
    (full O(users) rebuild behind the churn guard's scalar backoff).
    Also records publish→delivery latency of traced frames under churn
    (aggregated through scripts/trace_report.py --json)."""
    import tempfile

    from pushcdn_tpu.proto import trace as trace_lib
    from pushcdn_tpu.testing.routebench import forward_rate
    out: dict = {}
    results: dict = {}
    spans_dir = tempfile.mkdtemp(prefix="pushcdn-churnspans-")
    for mode, inc in (("incremental", True), ("rebuild", False)):
        spans_path = os.path.join(spans_dir, f"{mode}.jsonl")
        trace_lib._LOG_PATH, trace_lib._log_file = spans_path, None
        try:
            res = await forward_rate(
                "native", receivers=receivers, msgs=msgs, trials=trials,
                parked_users=parked_users, churn=True, incremental=inc,
                trace_every=sample, deliver_spans=True)
        finally:
            if trace_lib._log_file is not None:
                try:
                    trace_lib._log_file.close()
                except Exception:
                    pass
            trace_lib._LOG_PATH, trace_lib._log_file = None, None
        gc.collect()
        if res is None:
            emit("route/churn_forward", 0, "skipped", mode=mode,
                 reason="native route-plan kernel unavailable")
            return out
        results[mode] = res
        summary = res.get("route_summary") or {}
        emit("route/churn_forward", res["median"], "msgs/s",
             impl="native", mode=mode, receivers=receivers,
             msgs=res["msgs"], parked_users=parked_users,
             churn_ops_s=round(res["churn_ops_s"], 1),
             deltas_applied=summary.get("deltas_applied"),
             rebuilds=summary.get("rebuilds"),
             last_delta_apply_s=summary.get("last_delta_apply_s"),
             trials=[round(r, 1) for r in res["trials"]])
        # publish→delivery percentiles under churn, aggregated by the
        # REAL scripts/trace_report.py over the run's span log (the
        # traced frames' delivery-hop latency is measured from the
        # carried publish-time origin)
        report = await run_trace_report_on(spans_path)
        delivery = ((report or {}).get("per_hop") or {}).get("delivery")
        if delivery:
            emit("route/churn_e2e", delivery["p50_ms"], "ms", mode=mode,
                 tier="p50", samples=delivery.get("count"),
                 source="trace_report")
            emit("route/churn_e2e", delivery["p99_ms"], "ms", mode=mode,
                 tier="p99", samples=delivery.get("count"),
                 source="trace_report")
            out[f"churn_e2e_p99_ms_{mode}"] = delivery["p99_ms"]
    inc_med = results["incremental"]["median"]
    reb_med = results["rebuild"]["median"]
    if reb_med:
        ratio = inc_med / reb_med
        emit("route/churn_forward", ratio, "x",
             tier="incremental-vs-rebuild", parked_users=parked_users,
             note="acceptance: >= 2x at the same churn rate")
        out["churn_forward_ratio"] = round(ratio, 2)
    out["churn_forward_msgs_s"] = round(inc_med, 1)
    return out


async def run_trace_report_on(spans_path: str) -> Optional[dict]:
    """Aggregate a spans JSONL through the REAL scripts/trace_report.py
    (the claim 'p99 via trace_report' must run the actual tool)."""
    import subprocess
    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "trace_report.py")
    proc = subprocess.run(
        [sys.executable, script, "--json", spans_path],
        capture_output=True, text=True, timeout=120)
    # rc 1 just means "no chain carried every hop" (this harness's
    # receivers emit delivery spans only) — the per-hop stats still hold
    try:
        return json.loads(proc.stdout)
    except ValueError:
        return None


# ---------------------------------------------------------------------------
# tier 6 (ISSUE 7): the synthetic 1M-subscription control-plane harness —
# no sockets, the Connections + RouteState pair driven directly so the
# measured object is route-state maintenance itself
# ---------------------------------------------------------------------------

async def bench_million_subs(quick: bool) -> dict:
    """Scale check for the incremental control plane: ``n_users`` users x
    ~``topics_per_user`` Zipf-skewed topics (~1M subscriptions at full
    size), then (a) subscribe/unsubscribe churn, (b) a reconnect storm
    (2% of users drop + re-add; auth itself is excluded — in production
    those reconnects ride the warm BLS pk cache, see BASELINE round 6),
    (c) a DirectMap merge wave — measuring per-batch delta-apply latency
    (p50/p99), snapshot staleness (mutation -> snapshot current), the
    memory ceiling under the admission limiter (the connection budget
    refuses users past the cap), and event-loop health (max scheduling
    lag of a concurrent ticker must stay under the /healthz budget)."""
    from pushcdn_tpu.broker import connections as connections_mod
    from pushcdn_tpu.broker.admission import AdmissionControl
    from pushcdn_tpu.broker.tasks import cutthrough
    from pushcdn_tpu.native import routeplan
    from pushcdn_tpu.proto import def_ as def_mod
    from pushcdn_tpu.proto import flightrec

    if not routeplan.available():
        emit("route/million", 0, "skipped",
             reason="native route-plan kernel unavailable")
        return {}

    # Zipf sampling WITH replacement dedups to ~15.2 unique topics/user,
    # so 68K users is what actually crosses 1M live subscriptions in the
    # native table (asserted below) — 50K would peak at ~760K
    n_users = 8_000 if quick else 68_000
    topics_per_user = 20
    churn_ops = 2_000 if quick else 20_000
    storm_users = max(n_users // 50, 100)

    class _Conn:
        def __init__(self, rec):
            self.flightrec = rec

        def close(self):
            pass

    class _Broker:
        pass

    rng = np.random.default_rng(7)
    # Zipf-skewed topic popularity over the u8 space (hot topics get the
    # bulk of the 1M subscriptions, like a consensus deployment's vote/
    # proposal topics)
    zipf = 1.0 / np.arange(1, 257)
    zipf /= zipf.sum()
    topic_choices = rng.choice(256, size=(n_users, topics_per_user),
                               p=zipf)

    from pushcdn_tpu.proto.topic import TopicSpace
    broker = _Broker()
    broker.connections = connections_mod.Connections("pub:m/priv:m")
    broker.run_def = def_mod.testing_run_def(
        topics=TopicSpace(valid=frozenset(range(256))))
    broker.device_plane = None
    broker.admission = None
    conns = broker.connections
    rec = flightrec.FlightRecorder("million-harness")  # one shared seat
    conn = _Conn(rec)

    def rss_kib() -> int:
        # current VmRSS, not ru_maxrss: the high-water mark reflects
        # whatever earlier bench tier peaked highest, not this harness
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
        return 0

    gc.collect()
    rss0 = rss_kib()
    # peak-tracked: the allocator reuses pages freed by earlier bench
    # tiers, so an end-of-run point sample can under-report (even go
    # negative); the ceiling is judged against the harness's own peak
    rss_peak = {"kib": rss0}

    def rss_note() -> None:
        now = rss_kib()
        if now > rss_peak["kib"]:
            rss_peak["kib"] = now
    # the STATED ceiling the run must fit in (admission budget times a
    # generous per-subscription allowance + fixed slack) — asserted, so
    # a memory regression fails the bench rather than drifting silently
    ceiling_mib = 256 + n_users * topics_per_user * 600 / (1 << 20)
    adm = AdmissionControl(broker)
    adm.max_user_conns = n_users  # the limiter IS the memory ceiling
    loop_lag = {"max": 0.0}
    ticker_stop = False

    async def ticker():
        # the /healthz loop-lag proxy: a sleep(0.01) wakeup that should
        # never be late by more than the health budget (2.0 s default)
        while not ticker_stop:
            t0 = time.perf_counter()
            await asyncio.sleep(0.01)
            late = time.perf_counter() - t0 - 0.01
            if late > loop_lag["max"]:
                loop_lag["max"] = late

    tick_task = asyncio.create_task(ticker())
    try:
        # ---- phase 1: connect the herd (admission-gated) ----
        t0 = time.perf_counter()
        shed = 0
        for i in range(n_users + 200):  # 200 over budget: must be shed
            if adm.admit_user() is not None:
                shed += 1
                continue
            key = b"mu%06d" % i
            conns.add_user(key, conn,
                           [int(t) for t in topic_choices[i % n_users]])
            if i % 2048 == 2047:
                await asyncio.sleep(0)
        connect_s = time.perf_counter() - t0
        total_subs = sum(len(conns.user_topics.get_values_of_key(k))
                         for k in list(conns.users)[:64])  # sample only
        state = cutthrough.RouteState(broker,
                                      routeplan.RoutePlanner.create())
        t0 = time.perf_counter()
        assert state._refresh()
        build_s = time.perf_counter() - t0
        stats = state.planner.stats()
        emit("route/million", stats["live_subs"], "subscriptions",
             tier="build", users=conns.num_users, shed_over_budget=shed,
             connect_s=round(connect_s, 3),
             first_build_s=round(build_s, 3),
             avg_topics_sampled=round(total_subs / 64, 1))
        assert shed == 200, "admission budget must have refused the rest"
        if not quick:
            assert stats["live_subs"] >= 1_000_000, \
                f"full-size harness must cross 1M live subscriptions " \
                f"(got {stats['live_subs']})"
        rss_note()

        # ---- phase 2: subscribe/unsubscribe churn, batched applies ----
        apply_lat: list = []
        # snapshot staleness: oldest unreflected mutation -> snapshot
        # current again (the batch window PLUS the apply, i.e. what a
        # plan call could observe at worst)
        staleness: list = []
        users = list(conns.users.keys())
        t0 = time.perf_counter()
        batch_first_mut = None
        for op in range(churn_ops):
            key = users[int(rng.integers(0, len(users)))]
            t = int(rng.integers(0, 256))
            if batch_first_mut is None:
                batch_first_mut = time.perf_counter()
            if op % 2 == 0:
                conns.subscribe_user_to(key, [t])
            else:
                conns.unsubscribe_user_from(key, [t])
            if op % 16 == 15:  # batched per plan call, like the drain
                ta = time.perf_counter()
                assert state._refresh()
                done = time.perf_counter()
                apply_lat.append(done - ta)
                staleness.append(done - batch_first_mut)
                batch_first_mut = None
                if op % 1024 == 1023:
                    await asyncio.sleep(0)
        churn_s = time.perf_counter() - t0
        rss_note()
        lat = sorted(apply_lat)

        def pct(arr, q):
            return arr[min(int(q * len(arr)), len(arr) - 1)]

        stale = sorted(staleness)
        emit("route/million", round(churn_ops / churn_s, 1), "ops/s",
             tier="churn", batches=len(apply_lat),
             apply_p50_us=round(pct(lat, 0.5) * 1e6, 1),
             apply_p99_us=round(pct(lat, 0.99) * 1e6, 1),
             staleness_p50_us=round(pct(stale, 0.5) * 1e6, 1),
             staleness_p99_us=round(pct(stale, 0.99) * 1e6, 1),
             deltas_applied=state.deltas_applied,
             rebuilds=dict(state.rebuild_counts))

        # ---- phase 3: reconnect storm (drop + re-add 2% of users) ----
        storm = [users[int(i)] for i in
                 rng.integers(0, len(users), size=storm_users)]
        t0 = time.perf_counter()
        for key in storm:
            conns.remove_user(key)
        for j, key in enumerate(storm):
            conns.add_user(key, conn,
                           [int(t) for t in topic_choices[j % n_users]])
            if j % 64 == 63:
                ta = time.perf_counter()
                assert state._refresh()
                apply_lat.append(time.perf_counter() - ta)
        ta = time.perf_counter()
        assert state._refresh()
        storm_catchup_s = time.perf_counter() - ta
        storm_s = time.perf_counter() - t0
        rss_note()
        emit("route/million", round(len(storm) * 2 / storm_s, 1), "ops/s",
             tier="reconnect_storm", storm_users=len(storm),
             catchup_s=round(storm_catchup_s, 4),
             rebuilds=dict(state.rebuild_counts),
             note="auth excluded: production reconnects ride the warm "
                  "BLS pk cache (BASELINE r6)")

        # ---- wrap-up: memory ceiling + loop health ----
        gc.collect()
        rss_note()
        rss_mib = (rss_peak["kib"] - rss0) / 1024
        stats = state.planner.stats()
        ticker_stop = True
        await tick_task
        lag_budget = float(os.environ.get("PUSHCDN_HEALTH_LAG_MAX", "")
                           or 2.0)
        green = loop_lag["max"] < lag_budget
        emit("route/million", round(rss_mib, 1), "MiB",
             tier="memory", users=conns.num_users,
             ceiling_mib=round(ceiling_mib, 1),
             rss_abs_mib=round(rss_peak["kib"] / 1024, 1),
             live_subs=stats["live_subs"],
             index_entries=stats["list_entries"],
             dmap_live=stats["dmap_live"],
             max_loop_lag_ms=round(loop_lag["max"] * 1e3, 2),
             loop_lag_green=green, lag_budget_s=lag_budget)
        assert green, (f"event loop lag {loop_lag['max']:.3f}s breached "
                       f"the {lag_budget}s health budget")
        assert rss_mib < ceiling_mib, \
            f"RSS +{rss_mib:.1f} MiB breached the {ceiling_mib:.0f} MiB " \
            f"stated ceiling"
        return {
            "million_users": conns.num_users,
            "million_subs": stats["live_subs"],
            "million_apply_p99_us": round(pct(lat, 0.99) * 1e6, 1),
            "million_staleness_p99_us": round(pct(stale, 0.99) * 1e6, 1),
            "million_storm_catchup_s": round(storm_catchup_s, 4),
            "million_rss_mib": round(rss_mib, 1),
            "million_rss_ceiling_mib": round(ceiling_mib, 1),
            "million_max_loop_lag_ms": round(loop_lag["max"] * 1e3, 2),
        }
    finally:
        ticker_stop = True
        if not tick_task.done():
            tick_task.cancel()


# ---------------------------------------------------------------------------
# tier 7 (ISSUE 8): the device data plane — dense-vs-ragged delivery A/B
# (CPU twin) + the one-collective fused mesh tick (8-device dryrun)
# ---------------------------------------------------------------------------


def bench_device_delivery(quick: bool) -> dict:
    """Dense delivery-matrix sweep vs ragged paged walk, uniform and
    zipf topic popularity, on the CPU twin (jnp reference kernels; rows
    labeled with the backend they ran on — a host bench, not a chip run).

    The timed unit is what egress actually consumes per tick: dense pays
    the U x N kernel PLUS the np.nonzero bool-matrix re-scan; ragged pays
    pack + the page walk + the compact-pair extraction. Interest is a
    steady-state :class:`RaggedInterest` (subscriptions don't churn
    mid-tick), frames draw topics from the same popularity law as
    subscriptions — the zipf rows are the ISSUE 8 acceptance shape
    (skewed fan-out, >= 4K users on the full run)."""
    import jax
    import jax.numpy as jnp

    from pushcdn_tpu.ops.delivery_kernel import delivery_matrix_reference
    from pushcdn_tpu.ops.ragged_delivery import (
        RaggedInterest,
        ragged_delivery_pallas,
        ragged_delivery_reference,
        ragged_pairs,
        ragged_pairs_grouped,
        ragged_to_dense,
    )
    from pushcdn_tpu.parallel.frames import split_mask
    from pushcdn_tpu.proto.message import KIND_BROADCAST

    U = 1024 if quick else 4096
    N = 512 if quick else 2048
    T, W = 256, 8
    topics_per_user = 3
    trials = 3 if quick else 5
    ticks = 2 if quick else 3
    backend = jax.default_backend()
    out: dict = {}

    dense_fn = jax.jit(delivery_matrix_reference)
    ragged_fn = jax.jit(ragged_delivery_reference)

    for popularity in ("uniform", "zipf"):
        rng = np.random.default_rng(11)
        if popularity == "zipf":
            p = 1.0 / np.arange(1, T + 1)
            p /= p.sum()
        else:
            p = np.full(T, 1.0 / T)
        sub = rng.choice(T, size=(U, topics_per_user), p=p)
        masks = np.zeros((U, W), np.uint32)
        mask_ints = []
        for u in range(U):
            m = 0
            for t in sub[u]:
                m |= 1 << int(t)
            mask_ints.append(m)
            masks[u] = split_mask(m, W)
        local = np.ones(U, bool)
        ftopic = rng.choice(T, size=N, p=p)
        kind = np.full(N, KIND_BROADCAST, np.int32)
        tmask = np.zeros((N, W), np.uint32)
        for n in range(N):
            tmask[n] = split_mask(1 << int(ftopic[n]), W)
        dest = np.full(N, -1, np.int32)
        valid = np.ones(N, bool)

        ri = RaggedInterest(T, max_pages=8192)
        for u in range(U):
            ri.set_mask(u, mask_ints[u])
        if ri.overflowed:
            emit("device/delivery", 0, "skipped", popularity=popularity,
                 reason="page pool overflow at bench scale")
            continue

        masks_d, local_d = jnp.asarray(masks), jnp.asarray(local)
        tmask_d, kind_d = jnp.asarray(tmask), jnp.asarray(kind)
        dest_d = jnp.asarray(dest)

        # one equivalence check per popularity before timing anything
        walk = ri.pack(kind, tmask, dest, valid, page_round=64)
        assert not walk.spilled
        dense0 = np.asarray(dense_fn(masks_d, local_d, tmask_d, kind_d,
                                     dest_d))
        out_u, _cnt = ragged_fn(jnp.asarray(walk.pages),
                                jnp.asarray(walk.walk_page),
                                jnp.asarray(walk.walk_frame),
                                local_d, masks_d, tmask_d, kind_d, dest_d)
        got = ragged_to_dense(np.asarray(out_u), walk.walk_frame, U, N)
        assert (got == dense0).all(), "ragged != dense on the bench mix"
        pairs = int(dense0.sum())
        ri.release_transient()

        def dense_tick():
            d = np.asarray(dense_fn(masks_d, local_d, tmask_d, kind_d,
                                    dest_d))
            return np.nonzero(d)  # the egress pair scan the dense path pays

        def ragged_tick(grouped: bool):
            w = ri.pack(kind, tmask, dest, valid, page_round=64)
            ou, _ = ragged_fn(jnp.asarray(w.pages),
                              jnp.asarray(w.walk_page),
                              jnp.asarray(w.walk_frame),
                              local_d, masks_d, tmask_d, kind_d, dest_d)
            if grouped:
                res = ragged_pairs_grouped(np.asarray(ou), w, num_users=U)
            else:
                res = ragged_pairs(np.asarray(ou), w.walk_frame,
                                   num_users=U)
            ri.release_transient()
            return res

        # two ragged rows, labeled by ordering contract: "strict" keeps
        # per-user order identical to the dense plane (the DevicePlane
        # default); "per-topic" is the mask-group-factorized fast path
        # (cross-topic order within a tick relaxed — the opt-in knob)
        meds = {}
        variants = (("dense", None, None),
                    ("ragged", False, "strict"),
                    ("ragged", True, "per-topic"))
        for impl, grouped, order in variants:
            tick = dense_tick if impl == "dense" \
                else (lambda g=grouped: ragged_tick(g))
            tick()  # warm (compile + caches)
            rates = []
            for _ in range(trials):
                t0 = time.perf_counter()
                for _ in range(ticks):
                    tick()
                rates.append(ticks * N / (time.perf_counter() - t0))
            med = statistics.median(rates)
            key = impl if order is None else f"{impl}:{order}"
            meds[key] = med
            extra = {} if order is None else {"order": order}
            emit("device/delivery", med, "msgs/s", impl=impl,
                 popularity=popularity, users=U, frames=N, topics=T,
                 pairs=pairs, backend=backend, mode="cpu-twin",
                 trials=[round(r, 1) for r in rates], **extra)
        if meds.get("dense"):
            for order in ("strict", "per-topic"):
                ratio = meds[f"ragged:{order}"] / meds["dense"]
                emit("device/delivery", ratio, "x",
                     tier=f"ragged-vs-dense-{popularity}", order=order,
                     users=U, backend=backend, mode="cpu-twin")
                suffix = "" if order == "per-topic" else "_strict"
                out[f"delivery_ragged_vs_dense_{popularity}{suffix}"] = \
                    round(ratio, 2)

        # interpreter-mode Pallas row (recorded so the real-chip A/B is
        # one flag away; skipped-not-mislabeled when Pallas can't run)
        if popularity == "zipf":
            try:
                small = min(8, walk.n_walk) or 8
                t0 = time.perf_counter()
                ragged_delivery_pallas(
                    jnp.asarray(walk.pages), jnp.asarray(walk.walk_page[:small]),
                    jnp.asarray(walk.walk_frame[:small]), local_d, masks_d,
                    tmask_d, kind_d, dest_d, interpret=True)
                emit("device/delivery", small / (time.perf_counter() - t0),
                     "walk-entries/s", impl="ragged-pallas-interpret",
                     popularity=popularity, backend=backend, mode="cpu-twin",
                     note="interpreter walks the grid in Python; NOT a "
                          "chip measurement")
            except Exception as exc:
                emit("device/delivery", 0, "skipped",
                     impl="ragged-pallas-interpret",
                     reason=f"pallas unavailable: {exc!r}")
    return out


def bench_mesh_tick(quick: bool) -> dict:
    """The one-collective mesh hop, dryrun: an 8-shard virtual CPU mesh
    runs the fused lane step (one packed all_gather per tick) against the
    per-array schedule, with the collective count ASSERTED from the
    lowered program — the counted one-collective-per-tick invariant.
    Labeled mode=dryrun: virtual devices measure dispatch/fusion shape,
    not ICI."""
    import jax
    import jax.numpy as jnp

    from pushcdn_tpu.parallel import router as router_mod
    from pushcdn_tpu.parallel.crdt import ABSENT, CrdtState
    from pushcdn_tpu.parallel.frames import DirectBuckets, FrameRing
    from pushcdn_tpu.parallel.mesh import make_broker_mesh
    from pushcdn_tpu.parallel.router import (
        DirectIngress,
        IngressBatch,
        RouterState,
        count_collectives,
        make_mesh_lane_step,
    )

    out: dict = {}
    n = 8
    if len(jax.devices()) < n:
        emit("device/mesh_tick", 0, "skipped",
             reason=f"need {n} devices, have {len(jax.devices())}")
        return out
    mesh = make_broker_mesh(n)
    U, S, F, C = 64, 16, 256, 4
    owners = np.full((n, U), ABSENT, np.int32)
    versions = np.zeros((n, U), np.uint32)
    ids = np.full((n, U), ABSENT, np.int32)
    masks = np.zeros((n, U), np.uint32)
    for i in range(n):
        owners[i, i] = i
        versions[i, i] = 1
        ids[i, i] = i
        masks[i, i] = 0b1
    state = RouterState(
        CrdtState(jnp.asarray(owners), jnp.asarray(versions),
                  jnp.asarray(ids)), jnp.asarray(masks))
    parts = []
    for i in range(n):
        ring = FrameRing(slots=S, frame_bytes=F)
        for j in range(S // 2):
            ring.push_broadcast(b"b%d-%d" % (i, j), 0b1)
        parts.append(ring.take_batch())
    batch = IngressBatch(
        *[jnp.asarray(np.stack([getattr(x, f) for x in parts]))
          for f in ("bytes_", "kind", "length", "topic_mask", "dest",
                    "valid")])
    dparts = []
    for i in range(n):
        d = DirectBuckets(n, capacity=C, frame_bytes=F)
        d.push((i + 1) % n, b"d%d" % i, dest_slot=(i + 1) % n)
        dparts.append(d.take_batch())
    direct = DirectIngress(
        *[jnp.asarray(np.stack([getattr(x, f) for x in dparts]))
          for f in ("bytes_", "length", "dest", "valid")])
    live = jnp.ones((n, n), bool)

    trials = 3 if quick else 5
    ticks = 20 if quick else 50
    expected = None
    for label, fused in (("fused", True), ("per-array", False)):
        step = make_mesh_lane_step(mesh, gather_bytes=False, fused=fused)
        lowered = jax.jit(step).lower(state, (batch,), (direct,),
                                      live).as_text()
        collectives = count_collectives(lowered)
        if fused:
            assert collectives == 1, (
                f"fused mesh tick must be exactly ONE collective, "
                f"lowered to {collectives}")
        res = step(state, (batch,), (direct,), live)  # compile + warm
        jax.block_until_ready(res.lanes[0].deliver)
        total = int(np.asarray(res.lanes[0].deliver).sum()) \
            + int(np.asarray(res.direct_lanes[0].deliver).sum())
        if expected is None:
            expected = total
        assert total == expected, "fused and per-array ticks must agree"
        rates = []
        for _ in range(trials):
            t0 = time.perf_counter()
            for _ in range(ticks):
                res = step(state, (batch,), (direct,), live)
            jax.block_until_ready(res.lanes[0].deliver)
            rates.append(ticks / (time.perf_counter() - t0))
        med = statistics.median(rates)
        emit("device/mesh_tick", med, "ticks/s", impl=label,
             collectives=collectives, devices=n, backend="cpu",
             mode="dryrun", deliveries=total,
             trials=[round(r, 1) for r in rates])
        out[f"mesh_tick_{label.replace('-', '_')}_ticks_s"] = round(med, 1)
        out[f"mesh_tick_{label.replace('-', '_')}_collectives"] = collectives
    return out


# ---------------------------------------------------------------------------
# tier 2: end-to-end broker forwarding through the wire
# ---------------------------------------------------------------------------

async def bench_forward(impl: str, receivers: int, msgs: int,
                        trials: int) -> Optional[float]:
    # the measurement loop lives in pushcdn_tpu.testing.routebench so the
    # configs_bench headline row and bench.py's companion host row track
    # the SAME loop (no drifting copies)
    from pushcdn_tpu.testing.routebench import forward_rate
    res = await forward_rate(impl, receivers=receivers, msgs=msgs,
                             trials=trials)
    if res is None:
        emit("route/forward", 0, "skipped", impl=impl,
             reason="native route-plan kernel unavailable")
        return None
    emit("route/forward", res["median"], "msgs/s", impl=impl,
         receivers=receivers, msgs=res["msgs"], payload=res["payload"],
         delivered_msgs_s=round(res["delivered"], 1),
         trials=[round(r, 1) for r in res["trials"]],
         max=round(max(res["trials"]), 1))
    return res["median"]


async def bench_forward_decoded(impl: str, receivers: int, msgs: int,
                                trials: int) -> dict:
    """ISSUE 8 client-receive-residue row: the SAME forwarding loop, but
    receivers drain through the real client batch decode (zero-copy
    payload views) — the application-visible delivered/s, re-measured
    through ``receive_messages``' own code path."""
    from pushcdn_tpu.testing.routebench import forward_rate
    res = await forward_rate(impl, receivers=receivers, msgs=msgs,
                             trials=trials, client_decode=True)
    if res is None:
        emit("route/forward_decoded", 0, "skipped", impl=impl,
             reason="native route-plan kernel unavailable")
        return {}
    emit("route/forward_decoded", res["median"], "msgs/s", impl=impl,
         receivers=receivers, msgs=res["msgs"], payload=res["payload"],
         decode="receive_messages", zero_copy=True,
         delivered_msgs_s=round(res["delivered"], 1),
         trials=[round(r, 1) for r in res["trials"]],
         max=round(max(res["trials"]), 1))
    return {"forward_decoded_msgs_s": round(res["median"], 1),
            "forward_decoded_delivered_s": round(res["delivered"], 1)}


async def bench_io_plane(quick: bool) -> dict:
    """ISSUE 15 rows: the host I/O data plane A/B (asyncio vs io_uring).

    Four tiers, every uring row honestly skipped when the kernel denies
    io_uring (ENOSYS / seccomp EPERM) instead of mislabeling an asyncio
    run:

    - ``io/probe``: the capability probe itself (CI asserts this row).
    - ``route/forward_tcp``: the route/forward loop with user links over
      real loopback TCP, per io impl — the end-to-end A/B. Routing +
      framing CPU dominates this tier on a shared core, so the ratio
      understates the byte-path win.
    - ``io/stream``: raw RawStream throughput, no broker — the byte
      path itself.
    - ``io/syscalls_per_msg``: counted data-plane syscalls per delivered
      message (LD_PRELOAD interposer in a measurement subprocess; strace
      is absent here and /proc/self/io misses socket ops).
    """
    import subprocess

    from pushcdn_tpu.native import syscount
    from pushcdn_tpu.native import uring as nuring

    stats: dict = {}
    cap = nuring.probe()
    emit("io/probe", max(cap, 0), "bitmask",
         available=nuring.available(),
         zerocopy=nuring.zerocopy_supported(),
         errname=None if nuring.available() else nuring.probe_errname())
    stats["io_uring_available"] = nuring.available()
    impls = ["asyncio"] + (["uring"] if nuring.available() else [])
    if not nuring.available():
        reason = f"io_uring unavailable ({nuring.probe_errname()})"
        for row in ("route/forward_tcp", "io/stream",
                    "io/syscalls_per_kmsg"):
            emit(row, 0, "skipped", io_impl="uring", reason=reason)

    # Every measured tier runs in a FRESH child per impl: an earlier
    # uring run warms the allocator (its ring + pbuf mappings leave
    # reusable pages) and a following asyncio stream run measures up to
    # 2x faster in the same process — subprocess isolation removes the
    # ordering bias. The forwarding child also runs under the
    # LD_PRELOAD interposer, so one run yields both the rate row and
    # the counted syscalls-per-message row (strace is absent here and
    # /proc/self/io misses socket ops).
    lib = syscount.build()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def child(impl: str, extra: list) -> Optional[dict]:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        if lib is not None:
            env["LD_PRELOAD"] = str(lib)
        argv = [sys.executable, "-m", "pushcdn_tpu.testing.routebench",
                "--io-impl", impl, "--trials",
                str(2 if quick else 5)] + extra
        try:
            out = subprocess.run(
                argv, capture_output=True, text=True, timeout=600,
                env=env, cwd=repo).stdout.strip()
            return json.loads(out.splitlines()[-1])
        except (subprocess.SubprocessError, ValueError, IndexError):
            return None

    fwd: dict = {}
    spm: dict = {}
    for impl in impls:
        res = child(impl, ["--receivers", "8",
                           "--msgs", str(1_000 if quick else 4_000)])
        if res is None:
            emit("route/forward_tcp", 0, "skipped", io_impl=impl,
                 reason="measurement child failed")
            continue
        fwd[impl] = res["median"]
        emit("route/forward_tcp", res["median"], "msgs/s", io_impl=impl,
             receivers=res["receivers"], msgs=res["msgs"],
             payload=res["payload"],
             delivered_msgs_s=round(res["delivered"], 1),
             trials=[round(r, 1) for r in res["trials"]])
        if "syscalls_per_msg" in res:
            spm[impl] = res["syscalls_per_msg"]
            emit("io/syscalls_per_kmsg", res["syscalls_per_msg"] * 1e3,
                 "calls/kmsg", io_impl=impl,
                 syscalls={k: v for k, v in res["syscalls"].items() if v})
        elif lib is not None:
            emit("io/syscalls_per_kmsg", 0, "skipped", io_impl=impl,
                 reason="interposer inactive in child")
    if fwd.get("uring") and fwd.get("asyncio"):
        emit("io/ratio", fwd["uring"] / fwd["asyncio"], "x",
             tier="forward_tcp")
        stats["forward_tcp_uring_x"] = round(
            fwd["uring"] / fwd["asyncio"], 2)
    if spm.get("asyncio") and spm.get("uring"):
        emit("io/ratio", spm["asyncio"] / spm["uring"], "x",
             tier="syscalls_per_kmsg")
        stats["syscall_reduction_x"] = round(
            spm["asyncio"] / spm["uring"], 2)

    st: dict = {}
    for impl in impls:
        res = child(impl, ["--stream",
                           "--stream-mb", str(128 if quick else 256)])
        if res is None:
            emit("io/stream", 0, "skipped", io_impl=impl,
                 reason="measurement child failed")
            continue
        st[impl] = res["median"]
        emit("io/stream", res["median"], "MB/s", io_impl=impl,
             write_size=res["write_size"], total_mb=res["total_mb"],
             trials=[round(r, 1) for r in res["trials"]])
    if st.get("uring") and st.get("asyncio"):
        emit("io/ratio", st["uring"] / st["asyncio"], "x", tier="stream")
        stats["stream_uring_x"] = round(st["uring"] / st["asyncio"], 2)
    return stats


async def bench_pump_attribution(quick: bool) -> dict:
    """ISSUE 17 rows: the fused data-plane pump A/B + attribution.

    Both legs run io_uring + the native planner over real loopback TCP
    in fresh measurement children (same isolation rationale as
    :func:`bench_io_plane`), flipping exactly one variable — the pump:

    - ``route/pump_forward``: the 8-receiver forwarding row, pump
      off vs on.  End-to-end on a shared core this UNDERSTATES the
      broker-side win: the bench publisher and all 8 receivers are
      Python on the same core, so their drain cost bounds the rate
      (Amdahl) — which is exactly what the attribution rows below are
      for.
    - ``route/pump_attribution``: counted interpreter call transitions
      per 1k delivered messages (``sys.setprofile`` over one unmeasured
      wave), counted data-plane syscalls per 1k messages (LD_PRELOAD
      interposer), and the pump-hit vs residual-escalation split from
      the route plane's own counters.

    Every row is honestly skipped when the kernel denies io_uring or
    the composition can't engage — never a residual-path run mislabeled
    as a pump run (the measurement child refuses to report a "pump" leg
    whose pump never sent a frame)."""
    import subprocess

    from pushcdn_tpu.native import routeplan, syscount
    from pushcdn_tpu.native import uring as nuring

    stats: dict = {}
    reason = None
    if not nuring.available():
        reason = f"io_uring unavailable ({nuring.probe_errname()})"
    elif not routeplan.available():
        reason = "route-plan kernel unavailable"
    if reason is not None:
        for row in ("route/pump_forward", "route/pump_attribution"):
            emit(row, 0, "skipped", pump="auto", reason=reason)
        stats["pump_engaged"] = False
        return stats

    lib = syscount.build()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def child(pump: str) -> Optional[dict]:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        if lib is not None:
            env["LD_PRELOAD"] = str(lib)
        argv = [sys.executable, "-m", "pushcdn_tpu.testing.routebench",
                "--io-impl", "uring", "--route-impl", "native",
                "--pump", pump, "--receivers", "8", "--transitions",
                "--msgs", str(1_000 if quick else 4_000),
                "--trials", str(2 if quick else 5)]
        try:
            out = subprocess.run(
                argv, capture_output=True, text=True, timeout=600,
                env=env, cwd=repo).stdout.strip()
            return json.loads(out.splitlines()[-1])
        except (subprocess.SubprocessError, ValueError, IndexError):
            return None

    fwd: dict = {}
    for pump in ("off", "auto"):
        res = child(pump)
        if res is None:
            emit("route/pump_forward", 0, "skipped", pump=pump,
                 reason="measurement child failed (or pump never "
                        "engaged)" if pump == "auto"
                 else "measurement child failed")
            continue
        fwd[pump] = res
        label = "off" if pump == "off" else "on"
        emit("route/pump_forward", res["median"], "msgs/s", pump=label,
             io_impl="uring", route_impl="native",
             receivers=res["receivers"], msgs=res["msgs"],
             payload=res["payload"],
             delivered_msgs_s=round(res["delivered"], 1),
             trials=[round(r, 1) for r in res["trials"]])
        if "transitions_per_kmsg" in res:
            emit("route/pump_attribution", res["transitions_per_kmsg"],
                 "transitions/kmsg", pump=label)
        if "syscalls_per_msg" in res:
            emit("route/pump_attribution", res["syscalls_per_msg"] * 1e3,
                 "calls/kmsg", pump=label,
                 syscalls={k: v for k, v in res["syscalls"].items() if v})
    on = fwd.get("auto")
    if on is not None and on.get("pump_summary"):
        ps = on["pump_summary"]
        esc = sum(ps.get("escalations", {}).values())
        hit = ps.get("pump_frames", 0)
        emit("route/pump_attribution",
             hit / max(hit + esc, 1), "hit-ratio",
             pump_frames=hit, escalated_frames=esc,
             escalations=ps.get("escalations", {}),
             plan_calls=ps.get("pump_calls", 0))
        stats["pump_hit_ratio"] = round(hit / max(hit + esc, 1), 4)
        stats["pump_engaged"] = True
    if fwd.get("auto") and fwd.get("off"):
        r = fwd["auto"]["median"] / fwd["off"]["median"]
        emit("route/pump_ratio", r, "x", tier="forward_tcp",
             note="end-to-end on a shared core; bench clients bound "
                  "the rate, see route/pump_attribution")
        stats["pump_forward_x"] = round(r, 2)
        to = fwd["off"].get("transitions_per_kmsg")
        tn = fwd["auto"].get("transitions_per_kmsg")
        if to and tn:
            emit("route/pump_ratio", to / tn, "x",
                 tier="transitions_per_kmsg")
            stats["pump_transition_reduction_x"] = round(to / tn, 2)
    return stats


async def bench_telemetry_overhead(quick: bool) -> dict:
    """ISSUE 19 row: native-telemetry overhead on the PUMPED path.

    ``route/telemetry_overhead`` is the honest cost of the shm stage
    stamps + class accounting the pump pays per run: the same
    8-receiver pumped forwarding child as ``route/pump_forward``, with
    exactly one variable flipped — ``PUSHCDN_NATIVE_TELEMETRY`` (0 =
    no mmap, every C-side observe compiled out behind the null telem
    pointer; 1 = the shipped default). Legs are INTERLEAVED off/on in
    fresh measurement children because a shared core drifts thermally
    over the minutes this takes; each leg's figure is the median of
    its children's medians — 5 pairs in full mode, since single
    same-process draws on this shared core range +-10% (the r17 shard
    tier learned the same lesson) and the real C-side cost per observe
    is nanoseconds. Budget: <= 2% (the observability-plane budget
    every prior overhead row holds to).

    Skips loudly when io_uring / the planner / the pump can't engage —
    an unpumped run measures the Python writer path, where the native
    stamps never execute, and would be a mislabeled 0%."""
    import subprocess

    from pushcdn_tpu.native import routeplan
    from pushcdn_tpu.native import uring as nuring

    stats: dict = {}
    reason = None
    if not nuring.available():
        reason = f"io_uring unavailable ({nuring.probe_errname()})"
    elif not routeplan.available():
        reason = "route-plan kernel unavailable"
    if reason is not None:
        emit("route/telemetry_overhead", 0, "skipped", reason=reason)
        return stats

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def child(telemetry: str) -> Optional[dict]:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PUSHCDN_NATIVE_TELEMETRY=telemetry)
        argv = [sys.executable, "-m", "pushcdn_tpu.testing.routebench",
                "--io-impl", "uring", "--route-impl", "native",
                "--pump", "auto", "--receivers", "8",
                "--msgs", str(1_000 if quick else 3_000),
                "--trials", str(2 if quick else 3)]
        try:
            out = subprocess.run(
                argv, capture_output=True, text=True, timeout=600,
                env=env, cwd=repo).stdout.strip()
            return json.loads(out.splitlines()[-1])
        except (subprocess.SubprocessError, ValueError, IndexError):
            return None

    legs: dict = {"0": [], "1": []}
    pairs = 2 if quick else 5
    for _ in range(pairs):
        for telemetry in ("0", "1"):  # interleaved: off, on, off, on, ...
            res = child(telemetry)
            if res is not None:
                legs[telemetry].append(res["median"])
    if not (legs["0"] and legs["1"]):
        emit("route/telemetry_overhead", 0, "skipped",
             reason="measurement children failed (or pump never engaged)")
        return stats

    off_med = statistics.median(legs["0"])
    on_med = statistics.median(legs["1"])
    emit("route/telemetry_overhead", off_med, "msgs/s", telemetry="off",
         receivers=8, pump="auto",
         trials=[round(r, 1) for r in legs["0"]])
    emit("route/telemetry_overhead", on_med, "msgs/s", telemetry="on",
         receivers=8, pump="auto",
         trials=[round(r, 1) for r in legs["1"]])
    if on_med:
        ratio = off_med / on_med  # >1 = telemetry costs throughput
        emit("route/telemetry_overhead", ratio, "x",
             overhead_pct=round((ratio - 1) * 100, 2),
             budget_pct=2.0, interleaved_pairs=pairs)
        stats["telemetry_overhead_ratio"] = round(ratio, 4)
        stats["telemetry_overhead_pct"] = round((ratio - 1) * 100, 2)
        stats["telemetry_headline_msgs_s"] = round(on_med, 1)
    return stats


async def bench_audit_overhead(quick: bool) -> dict:
    """ISSUE 20 row: frame-fate ledger overhead on the forwarding path.

    ``route/audit_overhead`` is the cost of the conservation ledger's
    per-decision accounting (queued/fate counters, per-link sent/recv
    tables, the dequeue stamps in the writer) on the same 8-receiver
    forwarding child as ``route/pump_forward``, with exactly one
    variable flipped — ``PUSHCDN_LEDGER`` (0 = every fast-path returns
    before touching a counter; 1 = the shipped default). Legs are
    INTERLEAVED off/on in fresh measurement children (same thermal-
    drift rationale as the telemetry row); each leg's figure is the
    median of its children's medians. Budget: <= 2%, the
    observability-plane budget every prior overhead row holds to."""
    import subprocess

    from pushcdn_tpu.native import uring as nuring

    stats: dict = {}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    io_impl = "uring" if nuring.available() else "asyncio"

    def child(ledger: str) -> Optional[dict]:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PUSHCDN_LEDGER=ledger)
        argv = [sys.executable, "-m", "pushcdn_tpu.testing.routebench",
                "--io-impl", io_impl, "--route-impl", "auto",
                "--pump", "auto", "--receivers", "8",
                "--msgs", str(1_000 if quick else 3_000),
                "--trials", str(2 if quick else 3)]
        try:
            out = subprocess.run(
                argv, capture_output=True, text=True, timeout=600,
                env=env, cwd=repo).stdout.strip()
            return json.loads(out.splitlines()[-1])
        except (subprocess.SubprocessError, ValueError, IndexError):
            return None

    legs: dict = {"0": [], "1": []}
    pair_ratios: list = []
    pairs = 2 if quick else 7
    for _ in range(pairs):
        pair: dict = {}
        for ledger in ("0", "1"):  # interleaved: off, on, off, on, ...
            res = child(ledger)
            if res is not None:
                legs[ledger].append(res["median"])
                pair[ledger] = res["median"]
        if "0" in pair and "1" in pair and pair["1"]:
            # back-to-back children see the same thermal/scheduler state,
            # so the per-pair ratio cancels the slow drift that dominates
            # this shared core's minute-scale variance (single-leg medians
            # here range +-20%, an order of magnitude above the real cost)
            pair_ratios.append(pair["0"] / pair["1"])
    if not pair_ratios:
        emit("route/audit_overhead", 0, "skipped",
             reason="measurement children failed")
        return stats

    off_med = statistics.median(legs["0"])
    on_med = statistics.median(legs["1"])
    emit("route/audit_overhead", off_med, "msgs/s", ledger="off",
         receivers=8, io_impl=io_impl,
         trials=[round(r, 1) for r in legs["0"]])
    emit("route/audit_overhead", on_med, "msgs/s", ledger="on",
         receivers=8, io_impl=io_impl,
         trials=[round(r, 1) for r in legs["1"]])
    ratio = statistics.median(pair_ratios)  # >1 = ledger costs throughput
    emit("route/audit_overhead", ratio, "x",
         overhead_pct=round((ratio - 1) * 100, 2),
         budget_pct=2.0, interleaved_pairs=len(pair_ratios),
         pair_ratios=[round(r, 3) for r in pair_ratios])
    stats["audit_overhead_ratio"] = round(ratio, 4)
    stats["audit_overhead_pct"] = round((ratio - 1) * 100, 2)
    stats["audit_headline_msgs_s"] = round(on_med, 1)
    return stats


async def amain(quick: bool, impl_arg: str,
                out_json: Optional[str] = None,
                shard_rows: Optional[str] = None,
                churn_rows: bool = False,
                io_rows: bool = True) -> None:
    from pushcdn_tpu.bin.common import tune_gc
    tune_gc()
    impls = ("native", "python") if impl_arg == "auto" else (impl_arg,)

    # ISSUE 7: the synthetic 1M-subscription control-plane harness runs
    # FIRST — its memory-ceiling row is an RSS delta, and the forwarding
    # tiers below leave gigabytes of freed-but-resident pool pages that
    # allocator reuse would silently absorb the harness's footprint into
    stats: dict = {}
    if churn_rows:
        stats.update(await bench_million_subs(quick))
        gc.collect()

    plan_medians = await bench_plan(
        impls, n_users=64, n_frames=2048 if quick else 8192,
        trials=3 if quick else 5)
    if "native" in plan_medians and "python" in plan_medians \
            and plan_medians["python"]:
        emit("route/ratio", plan_medians["native"] / plan_medians["python"],
             "x", tier="plan")

    fwd: dict = {}
    for impl in impls:
        # 5 full-mode trials: single same-process draws on this shared
        # core range ±10% (BASELINE r12 methodology note) — the r11
        # regression row needs the median to out-vote throttle dips
        fwd[impl] = await bench_forward(
            impl, receivers=8, msgs=2_000 if quick else 10_000,
            trials=2 if quick else 5)
        gc.collect()
    if fwd.get("native") and fwd.get("python"):
        emit("route/ratio", fwd["native"] / fwd["python"], "x",
             tier="forward")

    # ISSUE 8 satellite: the 8-receiver row through the real client
    # decode (zero-copy receive path)
    from pushcdn_tpu.native import routeplan as _routeplan
    dec_impl = "native" if ("native" in impls
                            and _routeplan.available()) else "python"
    stats.update(await bench_forward_decoded(
        dec_impl, receivers=8, msgs=2_000 if quick else 10_000,
        trials=2 if quick else 3))
    gc.collect()

    # ISSUE 15: the host I/O data plane A/B (asyncio vs io_uring) —
    # forwarding over real TCP, the raw byte path, and counted
    # syscalls-per-message
    if io_rows:
        stats.update(await bench_io_plane(quick))
        gc.collect()

    # ISSUE 17: the fused data-plane pump A/B (pump off vs on at
    # io_uring + native planner) with syscall / interpreter-transition
    # attribution
    if io_rows:
        stats.update(await bench_pump_attribution(quick))
        gc.collect()

    # ISSUE 19: native-telemetry overhead A/B on the pumped path
    # (PUSHCDN_NATIVE_TELEMETRY off vs on, interleaved children)
    if io_rows:
        stats.update(await bench_telemetry_overhead(quick))
        gc.collect()

    # ISSUE 20: frame-fate ledger overhead A/B on the forwarding path
    # (PUSHCDN_LEDGER off vs on, interleaved children)
    stats.update(await bench_audit_overhead(quick))
    gc.collect()

    # ISSUE 8: the device data plane — dense-vs-ragged delivery A/B on
    # the CPU twin + the one-collective fused mesh tick (dryrun)
    stats.update(bench_device_delivery(quick))
    gc.collect()
    stats.update(bench_mesh_tick(quick))
    gc.collect()

    # trace-overhead A/B on the primary deployment path (native when it
    # compiled here; otherwise the scalar loops get the same row so the
    # budget is still tracked)
    from pushcdn_tpu.native import routeplan
    trace_impl = "native" if ("native" in impls
                              and routeplan.available()) else "python"
    await bench_trace_overhead(
        trace_impl, receivers=8, msgs=2_000 if quick else 10_000,
        trials=2 if quick else 3)

    # ISSUE 5: whole-observability-plane overhead (profiler + tracing +
    # e2e histogram) under the same ≤2% budget, plus e2e percentiles
    stats.update(await bench_profiler_overhead(
        trace_impl, receivers=8, msgs=2_000 if quick else 10_000,
        trials=2 if quick else 3))

    # ISSUE 7: forwarding under sustained subscribe churn (incremental
    # deltas vs the rebuild-guard baseline; the 1M harness ran first,
    # see above)
    if churn_rows:
        stats.update(await bench_churn_forward(
            receivers=8, msgs=1_500 if quick else 6_000,
            parked_users=1_500 if quick else 8_000,
            trials=2 if quick else 3))
        gc.collect()

    # ISSUE 6: multi-core shard scaling rows (real OS processes over TCP)
    if shard_rows != "none":
        counts = [int(x) for x in
                  (shard_rows or ("1,2" if quick else "1,2,4")).split(",")]
        stats.update(await bench_shard_scaling(
            counts, receivers=8, msgs=1_500 if quick else 6_000,
            trials=2 if quick else 3))

    if out_json:
        write_bench_json(out_json, "route_bench", stats, RESULTS)


def write_bench_json(path: str, section: str, headline: dict,
                     rows: list) -> None:
    """Merge this run's rows into a machine-readable bench trajectory
    file (``BENCH_r09.json``) — the per-round artifacts stop being
    hand-curated. Each producer owns one section key; a pre-existing
    file's other sections are preserved."""
    doc: dict = {}
    if os.path.exists(path):
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except (OSError, ValueError):
            doc = {}
    # the round number rides in the artifact name (BENCH_r18.json -> 18)
    # so a re-run into a new round's file never inherits a stale constant
    m = re.search(r"_r0*(\d+)\.json$", os.path.basename(path))
    doc.setdefault("round", int(m.group(1)) if m else 19)
    from pushcdn_tpu.testing.provenance import provenance
    doc[section] = {"headline": headline, "rows": rows,
                    "provenance": provenance()}
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=1)
        fh.write("\n")
    print(f"wrote {path} [{section}]", file=sys.stderr)


def main() -> None:
    # the mesh-tick dryrun tier needs 8 virtual CPU devices; the flag
    # must land before jax first initializes (all jax imports in this
    # bench are lazy, so here is early enough)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--route-impl", choices=["auto", "native", "python"],
                    default="auto",
                    help="which routing implementation(s) to bench; "
                         "'auto' runs the native-vs-python A/B")
    ap.add_argument("--out-json", default=None, metavar="PATH",
                    help="merge this run's rows + headline into a "
                         "machine-readable bench file (e.g. BENCH_r10.json)")
    ap.add_argument("--shard-rows", default=None, metavar="N,N,...",
                    help="shard counts for the route/shard_forward tier "
                         "(default 1,2,4; 1,2 with --quick; 'none' skips)")
    ap.add_argument("--churn-rows", action="store_true",
                    help="ISSUE 7 tiers: forwarding-under-churn A/B "
                         "(incremental deltas vs the rebuild-guard "
                         "baseline) + the synthetic 1M-subscription "
                         "control-plane harness")
    ap.add_argument("--no-io-rows", action="store_true",
                    help="skip the ISSUE 15 host-I/O (asyncio vs "
                         "io_uring) tiers")
    args = ap.parse_args()
    asyncio.run(amain(args.quick, args.route_impl, args.out_json,
                      args.shard_rows, args.churn_rows,
                      io_rows=not args.no_io_rows))


if __name__ == "__main__":
    main()
