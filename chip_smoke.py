#!/usr/bin/env python3
"""Chip smoke: the device-plane broker end to end on the accelerator.

    python chip_smoke.py                             # on a machine with a TPU
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny    # tier-1 dry run (CPU)

The quickest proof that the system still starts on the chip. Three legs,
one after another, each a child process that owns the chip alone and has
fully exited before the next starts (this parent never imports jax — a
parent that touched JAX would hold the chip and starve every child):

- **kernels** — every delivery path ``auto`` selects at the served
  shapes (dense at U=1024 x N=1024 x 8 mask words, the N=64 wide lane,
  the N=8 latency slice, the ragged walk over the same table), compiled
  for the device with ``interpret=False`` and compared bit for bit with
  ``delivery_matrix_reference`` / ``ragged_delivery_reference`` and a
  numpy evaluation of the same rule on seeded inputs; plus
  ``__graft_entry__.entry()``.
- **served** — real ``bin/marshal`` + one ``bin/broker --device-plane``
  (all ``DevicePlaneConfig`` defaults) + SQLite discovery + 1,000 real
  TCP subscribers in ``testing/clientpack`` processes. One publisher
  sends pipelined bursts: 1 KB broadcasts on every topic, one burst of
  10 KB broadcasts (the 16 KiB lane), one burst of directs. Pass = every
  subscriber's gap detector ends with zero residual and its unique count
  equals what this script computed from the subscription table; the
  broker's /metrics names the device, counts steps, and its device
  deliveries equal fan-out x frames staged in every burst; the plane
  never disabled; the broker exits 0 on SIGTERM.
- **mesh** — only where >= 4 devices are visible: the in-process
  ``MeshBrokerGroup`` at ``MeshGroupConfig`` defaults over four chips,
  users on every shard, cross-shard bursts of broadcasts and directs.

Without ``--tiny`` a run that finds no accelerator fails and prints no
result. The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
BUDGET_S = 1150.0  # the whole script, compilation and native builds included
PUBLISHER_SEED = 900_000
PACK_SEED_BASE = 100_000

# The served leg. Each clientpack holds as many subscribers as there are
# topics, so every topic has exactly ``packs`` subscribers: a burst's
# device deliveries must then equal packs x (frames staged), whichever
# frames the idle bypass sent down the host path instead.
FULL = dict(packs=4, topics=250, rounds=3, per_topic=1, big=64, directs=64)
TINY = dict(packs=2, topics=12, rounds=2, per_topic=2, big=8, directs=8)
SMALL_PAYLOAD, BIG_PAYLOAD, DIRECT_PAYLOAD = 1000, 10_000, 256


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# leg: kernels (child process — owns the chip)
# ---------------------------------------------------------------------------


def _np_delivery(umask, local, tmask, kind, dest):
    """The delivery rule in numpy — independent of the code under test."""
    import numpy as np
    from pushcdn_tpu.proto.message import KIND_BROADCAST, KIND_DIRECT
    hit_b = ((umask[:, None, :] & tmask[None, :, :]) != 0).any(-1)
    hit_d = dest[None, :] == np.arange(len(local))[:, None]
    return local[:, None] & (((kind == KIND_BROADCAST)[None, :] & hit_b)
                             | ((kind == KIND_DIRECT)[None, :] & hit_d))


def _seeded_table(rng, U, W):
    import numpy as np
    masks = np.zeros((U, W), np.uint32)
    for u in range(U):
        for t in rng.integers(0, 32 * W, rng.integers(1, 4)):
            masks[u, t // 32] |= np.uint32(1 << (t % 32))
    local = rng.random(U) < 0.9
    return masks, local


def _seeded_frames(rng, N, U, W):
    import numpy as np
    from pushcdn_tpu.proto.message import KIND_BROADCAST, KIND_DIRECT
    kind = rng.choice([KIND_BROADCAST, KIND_BROADCAST, KIND_DIRECT, 0],
                      N).astype(np.int32)
    tmask = np.zeros((N, W), np.uint32)
    for n in range(N):
        for t in rng.integers(0, 32 * W, 1 if n % 8 else 3):
            tmask[n, t // 32] |= np.uint32(1 << (t % 32))
    dest = rng.integers(0, U, N).astype(np.int32)
    return kind, tmask, dest


def leg_kernels(tiny: bool) -> dict:
    import numpy as np
    from pushcdn_tpu.parallel import runtime
    rt = runtime.init("chip_smoke kernels")
    import jax
    import jax.numpy as jnp

    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from pushcdn_tpu.ops import delivery_kernel as dk
    from pushcdn_tpu.ops import ragged_delivery as rd

    on_tpu = rt.device.platform == "tpu"
    cfg = DevicePlaneConfig()
    W = cfg.topic_words
    U = 64 if tiny else cfg.num_user_slots
    # the shapes the served plane steps at: base lane, wide lane and the
    # latency slice
    lanes = [128 if tiny else cfg.ring_slots, cfg.extra_lanes[0][1],
             cfg.latency_slots]
    # off the chip the Pallas kernels run through the interpreter (auto
    # would pick the jnp twins there); on the chip auto decides and
    # nothing is ever interpreted
    force = None if on_tpu else True
    rng = np.random.default_rng(21)
    masks, local = _seeded_table(rng, U, W)
    checks = []

    def timed(fn, *args):
        before = rt.compiles.seconds
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        first = time.perf_counter() - t0
        compile_s = rt.compiles.seconds - before
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        return out, round(compile_s, 3), round(first, 3), \
            round(time.perf_counter() - t0, 5)

    for N in lanes:
        kind, tmask, dest = _seeded_frames(rng, N, U, W)
        args = tuple(jnp.asarray(a) for a in (masks, local, tmask, kind, dest))
        impl = "pallas" if dk.selects_pallas(U, N, force) else "xla"
        got, compile_s, first_s, run_s = timed(jax.jit(
            lambda *a: dk.delivery_matrix(*a, use_pallas=force)), *args)
        ref = np.asarray(jax.jit(dk.delivery_matrix_reference)(*args))
        host = _np_delivery(masks, local, tmask, kind, dest)
        ok = bool((np.asarray(got) == ref).all() and (ref == host).all())
        checks.append({"kernel": f"dense U={U} N={N} W={W}", "impl": impl,
                       "interpret": impl == "pallas" and not on_tpu,
                       "compile_s": compile_s, "first_call_s": first_s,
                       "run_s": run_s, "deliveries": int(host.sum()),
                       "match": ok})

    # ragged: a packed walk over the same user table and base-lane
    # frames — at the full table and at the 64-user bucket a ragged
    # plane warms up with (the kernel's table chunking depends on U)
    N = lanes[0]
    kind, tmask, dest = _seeded_frames(rng, N, U, W)
    for users in sorted({U, 64}, reverse=True):
        index = rd.RaggedInterest(32 * W, max_pages=cfg.ragged_max_pages)
        for u in range(users):
            index.set_mask(u, int.from_bytes(masks[u].tobytes(), "little"))
        walk = index.pack(kind, tmask, dest, kind != 0, page_round=64)
        check(not walk.spilled and not index.overflowed,
              "ragged page pool could not hold the smoke's walk")
        args = tuple(jnp.asarray(a) for a in (
            walk.pages, walk.walk_page, walk.walk_frame, local[:users],
            masks[:users], tmask, kind, dest))
        impl = "pallas" if rd.ragged_selects_pallas(force) else "xla"
        got, compile_s, first_s, run_s = timed(jax.jit(
            lambda *a: rd.ragged_delivery(*a, use_pallas=force)), *args)
        ref = jax.jit(rd.ragged_delivery_reference)(*args)
        dense = rd.ragged_to_dense(np.asarray(got[0]), walk.walk_frame,
                                   users, N)
        host = _np_delivery(masks[:users], local[:users], tmask, kind, dest)
        ok = bool((np.asarray(got[0]) == np.asarray(ref[0])).all()
                  and (np.asarray(got[1]) == np.asarray(ref[1])).all()
                  and (dense == host).all())
        checks.append({
            "kernel": f"ragged U={users} N={N} W={W} walk={walk.n_walk}",
            "impl": impl, "interpret": impl == "pallas" and not on_tpu,
            "compile_s": compile_s, "first_call_s": first_s,
            "run_s": run_s, "deliveries": int(host.sum()), "match": ok})

    import __graft_entry__ as graft
    fn, example = graft.entry()
    out, compile_s, first_s, run_s = timed(fn, *example)
    checks.append({"kernel": "__graft_entry__.entry()", "impl": "step",
                   "interpret": False, "compile_s": compile_s,
                   "first_call_s": first_s, "run_s": run_s,
                   "deliveries": int(np.asarray(out.deliver).sum()),
                   "match": int(np.asarray(out.deliver).sum()) == 2})
    for c in checks:
        say(f"kernels: {c}")
    return {"leg": "kernels", "ok": all(c["match"] for c in checks),
            "device": rt.device._asdict(), "compile_cache": rt.cache_dir,
            "compile": rt.compiles.snapshot(), "checks": checks}


# ---------------------------------------------------------------------------
# leg: mesh (child process — owns all four chips)
# ---------------------------------------------------------------------------


async def leg_mesh(tiny: bool) -> dict:
    import numpy as np
    from pushcdn_tpu.parallel import runtime
    rt = runtime.init("chip_smoke mesh")
    import jax

    from pushcdn_tpu.broker.mesh_group import MeshGroupConfig
    from pushcdn_tpu.parallel.router import count_collectives
    from pushcdn_tpu.testing.mesh_cluster import MeshCluster

    check(rt.device.count >= 4, f"mesh leg needs 4 devices, JAX shows "
                                f"{rt.device.count}")
    shards = 4
    devices = jax.devices()[:shards]
    if tiny:
        cluster = MeshCluster(num_shards=shards, devices=devices)
        per_shard, burst, big, directs = 2, 8, 0, 4
    else:  # MeshGroupConfig defaults, not MeshCluster's toy sizes
        d = MeshGroupConfig()
        cluster = MeshCluster(
            num_shards=shards, devices=devices,
            num_user_slots=d.num_user_slots, ring_slots=d.ring_slots,
            frame_bytes=d.frame_bytes, extra_lanes=d.extra_lanes,
            batch_window_s=d.batch_window_s)
        per_shard, burst, big, directs = 16, 64, 16, 32
    topics = 2  # testing_run_def knows TestTopic Global=0 and DA=1
    t_start = time.perf_counter()
    await cluster.start()
    group = cluster.group
    warm = rt.compiles.snapshot()
    seen = []  # (args, result) of every step after warm-up
    step_fn = group.step_fn

    def spy(*args):
        result = step_fn(*args)
        seen.append((args, result))
        del seen[:-1]
        return result

    group.step_fn = spy
    try:
        clients = {}  # (shard, j) -> Client
        for s in range(shards):
            for j in range(per_shard):
                clients[s, j] = await cluster.place_client(
                    seed=7000 + s * 100 + j, shard=s, topics=[j % topics])
        expected = {key: [] for key in clients}

        def broadcast(topic, payload):
            for (s, j), _c in clients.items():
                if j % topics == topic:
                    expected[s, j].append(payload)

        t_traffic = time.perf_counter()
        sent = 0
        # cross-shard broadcasts in bursts, from two different shards
        for src in (0, 2):
            for k in range(burst):
                payload = b"B%d.%03d." % (src, k) + b"x" * 900
                await clients[src, 0].send_broadcast_message(
                    [k % topics], payload)
                broadcast(k % topics, payload)
                sent += 1
        for k in range(big):  # the wide lane
            payload = b"W1.%03d." % k + b"y" * 10_000
            await clients[1, 0].send_broadcast_message([k % topics], payload)
            broadcast(k % topics, payload)
            sent += 1
        # cross-shard directs in bursts: shard s -> users of shard s+1
        for s in range(shards):
            for k in range(directs):
                dst = ((s + 1) % shards, k % per_shard)
                payload = b"D%d.%03d." % (s, k) + b"z" * 200
                await clients[s, 0].send_direct_message(
                    clients[dst].public_key, payload)
                expected[dst].append(payload)
                sent += 1

        async def drain(key):
            got = []
            want = len(expected[key])
            async with asyncio.timeout(120):
                while len(got) < want:
                    for m in await clients[key].receive_messages():
                        got.append(bytes(m.message))
            return key, got

        received = dict(await asyncio.gather(*(drain(k) for k in clients)))
        await asyncio.sleep(0.5)  # anything extra would land now
        run_s = time.perf_counter() - t_traffic
        for key, got in received.items():
            check(sorted(got) == sorted(expected[key]),
                  f"mesh client {key}: got {len(got)} frames, expected "
                  f"{len(expected[key])} (or wrong payloads)")
        deliveries = sum(len(v) for v in expected.values())
        check(group.steps > 0, "mesh group never stepped")
        check(not group.disabled, "mesh group disabled itself")
        check(group.frames_staged == sent,
              f"mesh group staged {group.frames_staged} of {sent} frames")
        check(group.messages_routed == deliveries,
              f"mesh group routed {group.messages_routed} deliveries, "
              f"expected {deliveries}")
        args, result = seen[-1]
        out_devices = sorted(
            (d.platform, d.id)
            for d in result.lanes[0].deliver.sharding.device_set)
        check(len(out_devices) == shards
              and {p for p, _ in out_devices} == {rt.device.platform},
              f"step outputs live on {out_devices}")
        lowered = count_collectives(step_fn.lower(*args).as_text())
        check(lowered == 1 and group.collectives_last_trace == 1,
              f"fused tick holds {lowered} collectives (traced "
              f"{group.collectives_last_trace})")
    finally:
        for c in clients.values():
            c.close()
        await cluster.stop()
    import __graft_entry__ as graft
    graft.dryrun_multichip(shards)
    total = rt.compiles.snapshot()
    report = {
        "leg": "mesh", "ok": True, "device": rt.device._asdict(),
        "shards": shards, "users": len(clients), "frames_staged": sent,
        "device_deliveries": deliveries, "steps": group.steps,
        "output_devices": out_devices, "collectives_per_tick": lowered,
        "warmup_compile_s": warm["compile_s"], "compile": total,
        "run_s": round(run_s, 3),
        "wall_s": round(time.perf_counter() - t_start, 3),
        "config": "toy (MeshCluster defaults)" if tiny else
                  "MeshGroupConfig defaults",
    }
    return report


# ---------------------------------------------------------------------------
# the parent: orchestration only, never imports jax
# ---------------------------------------------------------------------------


class Procs:
    """Every process the smoke starts, so all of them are stopped."""

    def __init__(self):
        self.all = []

    def add(self, name, proc):
        self.all.append((name, proc))
        return proc

    @staticmethod
    def stop(name, proc, grace_s=45.0):
        """SIGTERM and wait — never SIGKILL a chip owner while it can
        still answer (a killed owner leaves the libtpu lock behind).
        Returns the exit code."""
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                say(f"{name} ignored SIGTERM for {grace_s:.0f}s; killing")
                proc.kill()
                proc.wait(timeout=30)
        return proc.returncode

    def stop_all(self):
        for name, proc in reversed(self.all):
            self.stop(name, proc)


def _http(port, path, timeout=5.0):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read().decode()
    except (urllib.error.URLError, OSError):
        return None, ""


def _metric(text, name, **labels):
    """Value of one series of a Prometheus text page (None if absent)."""
    for line in text.splitlines():
        if line.startswith("#") or not line.startswith(name):
            continue
        head, _, value = line.rpartition(" ")
        if head == name or (head.startswith(name + "{") and all(
                f'{k}="{v}"' in head for k, v in labels.items())):
            return float(value)
    return None


def run_leg_child(leg, tiny, deadline, procs):
    """Run ``--leg <leg>`` as a child, echo its output, and return the
    JSON report on its last line (SmokeFailure if it exits non-zero)."""
    argv = [sys.executable, os.path.abspath(__file__), "--leg", leg]
    if tiny:
        argv.append("--tiny")
    proc = procs.add(leg, subprocess.Popen(
        argv, stdout=subprocess.PIPE, text=True, cwd=REPO))
    try:
        out, _ = proc.communicate(timeout=max(deadline - time.monotonic(), 1))
    except subprocess.TimeoutExpired:
        Procs.stop(leg, proc)
        raise SmokeFailure(f"leg {leg} ran out of time")
    sys.stdout.write(out)
    sys.stdout.flush()
    check(proc.returncode == 0, f"leg {leg} exited {proc.returncode}")
    report = json.loads(out.strip().splitlines()[-1])
    check(report.get("ok") is True, f"leg {leg} reported failure")
    return report


class Pack:
    """One clientpack process: JSON events out, one-word commands in."""

    def __init__(self, proc):
        self.proc = proc
        self.events = asyncio.Queue()
        self.reader = asyncio.create_task(self._read())

    async def _read(self):
        while True:
            line = await self.proc.stdout.readline()
            if not line:
                await self.events.put({"event": "eof"})
                return
            try:
                await self.events.put(json.loads(line))
            except ValueError:
                pass  # a stray print is not protocol

    async def expect(self, event, timeout):
        async with asyncio.timeout(timeout):
            while True:
                ev = await self.events.get()
                if ev["event"] == event:
                    return ev
                check(ev["event"] != "eof",
                      f"clientpack exited while waiting for {event!r}")

    async def command(self, word, event, timeout=30.0):
        self.proc.stdin.write(word.encode() + b"\n")
        await self.proc.stdin.drain()
        return await self.expect(event, timeout)


async def leg_served(tiny, device, deadline, procs, workdir):
    from pushcdn_tpu.bin.common import free_ports, spawn_binary
    from pushcdn_tpu.client import Client, ClientConfig
    from pushcdn_tpu.proto.crypto.signature import DEFAULT_SCHEME
    from pushcdn_tpu.proto.transport import Tcp

    size = TINY if tiny else FULL
    packs_n, topics = size["packs"], size["topics"]
    subscribers = packs_n * topics
    db = os.path.join(workdir, "discovery.sqlite")
    pub, priv, metrics, marshal_port = free_ports(4)
    t_start = time.monotonic()

    def left(cap):
        return max(min(cap, deadline - time.monotonic()), 1.0)

    broker = procs.add("broker", spawn_binary(
        "broker", "--discovery-endpoint", db,
        "--public-advertise-endpoint", f"127.0.0.1:{pub}",
        "--public-bind-endpoint", f"127.0.0.1:{pub}",
        "--private-advertise-endpoint", f"127.0.0.1:{priv}",
        "--private-bind-endpoint", f"127.0.0.1:{priv}",
        "--metrics-bind-endpoint", f"127.0.0.1:{metrics}",
        "--user-transport", "tcp", "--device-plane",
        log_path=os.path.join(workdir, "broker.log")))

    def topology():
        status, body = _http(metrics, "/debug/topology")
        return json.loads(body) if status == 200 else None

    # warm-up (compile + first step) must finish before users arrive
    plane = None
    end = time.monotonic() + left(600)
    while time.monotonic() < end:
        check(broker.poll() is None,
              f"broker exited {broker.returncode} during start-up — see "
              f"{workdir}/broker.log")
        topo = await asyncio.to_thread(topology)
        if topo and topo["device_plane"] and \
                topo["device_plane"]["warmup_s"] is not None:
            plane = topo["device_plane"]
            break
        await asyncio.sleep(0.5)
    check(plane is not None, "broker's device plane never finished warm-up")
    start_s = time.monotonic() - t_start
    say(f"served: broker up in {start_s:.1f}s, plane {plane}")
    check(plane["platform"] == device["platform"]
          and plane["device_kind"] == device["kind"],
          f"broker runs on {plane['platform']}/{plane['device_kind']}, the "
          f"kernels leg saw {device}")

    procs.add("marshal", spawn_binary(
        "marshal", "--discovery-endpoint", db,
        "--bind-endpoint", f"127.0.0.1:{marshal_port}",
        "--user-transport", "tcp",
        log_path=os.path.join(workdir, "marshal.log")))
    publisher = Client(ClientConfig(
        marshal_endpoint=f"127.0.0.1:{marshal_port}",
        keypair=DEFAULT_SCHEME.generate_keypair(seed=PUBLISHER_SEED),
        protocol=Tcp))
    async with asyncio.timeout(left(120)):
        await publisher.ensure_initialized()

    env = {**os.environ, "PYTHONPATH": REPO + (
        os.pathsep + os.environ["PYTHONPATH"]
        if os.environ.get("PYTHONPATH") else "")}
    packs = []

    async def drive():
        for p in range(packs_n):
            with open(os.path.join(workdir, f"pack{p}.log"), "ab") as errlog:
                proc = await asyncio.create_subprocess_exec(
                    sys.executable, "-m", "pushcdn_tpu.testing.clientpack",
                    "--marshal-endpoint", f"127.0.0.1:{marshal_port}",
                    "--clients", str(topics), "--topics", str(topics),
                    "--seed-base", str(PACK_SEED_BASE + p * topics),
                    "--report-every-s", "3600", "--settle-s", "0.5",
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    stderr=errlog, env=env)
            packs.append(Pack(proc))
        for pack in packs:
            await pack.expect("ready", left(400))
        connect_s = time.monotonic() - t_start - start_s
        say(f"served: {subscribers} subscribers connected in {connect_s:.1f}s")

        async def scrape():
            status, text = await asyncio.to_thread(_http, metrics, "/metrics")
            check(status == 200, "broker /metrics does not answer")
            check(_metric(text, "cdn_device_plane_disabled") == 0,
                  "the device plane disabled itself")
            return {"staged": _metric(text, "cdn_device_frames_staged"),
                    "routed": _metric(text, "cdn_device_messages_routed"),
                    "steps": _metric(text, "cdn_device_steps"),
                    "users": _metric(text, "cdn_num_users_connected"),
                    "text": text}

        base = await scrape()
        check(base["users"] == subscribers + 1,
              f"broker counts {base['users']} users, not {subscribers + 1}")
        topo = await asyncio.to_thread(topology)
        check(topo["device_plane"]["unmirrored_users"] == 0,
              "the slot table left users unmirrored")

        # the subscription table, as this script knows it: pack p, client i
        # holds key seed PACK_SEED_BASE + p*topics + i and topic i
        expect_unique = [[0] * topics for _ in range(packs_n)]
        next_seq = [0] * topics
        total_expected = 0

        def payload(seq, size_bytes):
            return seq.to_bytes(4, "big") + b"s" * (size_bytes - 4)

        async def delivered(total):
            end = time.monotonic() + left(120)
            seen = -1
            while time.monotonic() < end:
                marks = [await pk.command("mark", "mark") for pk in packs]
                seen = sum(m["unique"] for m in marks)
                if seen >= total:
                    return
                await asyncio.sleep(0.2)
            raise SmokeFailure(f"subscribers hold {seen} unique frames, "
                               f"expected {total}")

        bursts = []

        async def burst(name, fanout, send):
            """Run one pipelined burst (``send`` writes every frame back to
            back, no await yields between them), wait until the subscribers
            hold everything, then hold the device's counters to it."""
            nonlocal total_expected
            before = await scrape()
            t0 = time.monotonic()
            frames, deliveries = await send()
            total_expected += deliveries
            await delivered(total_expected)
            after = await scrape()
            staged = int(after["staged"] - before["staged"])
            routed = int(after["routed"] - before["routed"])
            row = {"burst": name, "frames": frames, "staged": staged,
                   "expected_deliveries": deliveries,
                   "device_deliveries": routed,
                   "steps": int(after["steps"] - before["steps"]),
                   "wall_s": round(time.monotonic() - t0, 3)}
            say(f"served: {row}")
            check(0 < staged <= frames,
                  f"burst {name}: {staged} of {frames} frames staged")
            check(routed == fanout * staged,
                  f"burst {name}: device delivered {routed}, expected "
                  f"{fanout} x {staged} staged frames")
            bursts.append(row)

        def small_round():
            async def send():
                for t in range(topics):
                    for _ in range(size["per_topic"]):
                        await publisher.send_broadcast_message(
                            [t], payload(next_seq[t], SMALL_PAYLOAD))
                        next_seq[t] += 1
                for per_pack in expect_unique:
                    for t in range(topics):
                        per_pack[t] += size["per_topic"]
                frames = topics * size["per_topic"]
                return frames, frames * packs_n
            return send

        async def big_burst():
            for t in range(size["big"]):
                await publisher.send_broadcast_message(
                    [t], payload(next_seq[t], BIG_PAYLOAD))
                next_seq[t] += 1
                for per_pack in expect_unique:
                    per_pack[t] += 1
            return size["big"], size["big"] * packs_n

        async def direct_burst():
            # distinct subscribers: clients 0..n-1 of pack 0; each direct
            # continues its recipient's own topic sequence
            for i in range(size["directs"]):
                key = DEFAULT_SCHEME.generate_keypair(
                    seed=PACK_SEED_BASE + i).public_key
                await publisher.send_direct_message(
                    key, payload(next_seq[i], DIRECT_PAYLOAD))
                expect_unique[0][i] += 1
            return size["directs"], size["directs"]

        for r in range(size["rounds"]):
            await burst(f"1KB round {r}", packs_n, small_round())
        await burst("10KB", packs_n, big_burst)
        await burst("directs", 1, direct_burst)

        results = [await pk.command("finish", "result", left(60))
                   for pk in packs]
        for p, (pk, res) in enumerate(zip(packs, results)):
            check(res["gaps"] == 0, f"pack {p}: {res['gaps']} residual gaps")
            check(res["hard_reconnects"] == 0,
                  f"pack {p}: {res['hard_reconnects']} lost connections")
            check(res["unique_by_client"] == expect_unique[p],
                  f"pack {p}: per-subscriber unique counts differ from the "
                  f"subscription table's")
            await pk.proc.wait()
        final = await scrape()
        check(final["steps"] > 0, "cdn_device_steps is 0")
        check(_metric(final["text"], "cdn_build_info",
                      backend=device["platform"],
                      device_kind=device["kind"]) == 1,
              f"cdn_build_info does not name {device}")
        topo = await asyncio.to_thread(topology)
        plane = topo["device_plane"]
        rc = await asyncio.to_thread(Procs.stop, "broker", broker)
        check(rc == 0, f"broker exited {rc} on SIGTERM")
        staged = sum(b["staged"] for b in bursts)
        frames = sum(b["frames"] for b in bursts)
        check(staged * 10 >= frames * 9,
              f"only {staged} of {frames} frames reached the device")
        report = {
            "leg": "served", "ok": True,
            "device": {"platform": plane["platform"],
                       "kind": plane["device_kind"],
                       "count": plane["device_count"]},
            "subscribers": subscribers, "users_connected": subscribers + 1,
            "frames_sent": frames, "frames_staged": staged,
            "device_deliveries": sum(b["device_deliveries"] for b in bursts),
            "deliveries_expected": total_expected,
            "device_steps": int(final["steps"]),
            "kernels": plane["kernels"],
            "delivery_impl": plane["delivery_impl"],
            "compile_cache": plane.get("compile_cache"),
            "compile": {k: plane.get(k) for k in (
                "compile_s", "programs", "cache_hits", "cache_misses")},
            "warmup_s": plane["warmup_s"], "broker_start_s": round(start_s, 1),
            "connect_s": round(connect_s, 1), "bursts": bursts,
            "broker_exit": rc,
        }
        print(json.dumps(report), flush=True)
        return report

    try:
        return await drive()
    finally:
        publisher.close()
        for pk in packs:  # asyncio children: stopped on this loop
            pk.reader.cancel()
            if pk.proc.returncode is None:
                pk.proc.terminate()
                try:
                    await asyncio.wait_for(pk.proc.wait(), 20)
                except asyncio.TimeoutError:
                    pk.proc.kill()
                    await pk.proc.wait()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU dry run at a tiny size (tier-1); needs an "
                         "explicit JAX_PLATFORMS=cpu")
    ap.add_argument("--leg", choices=("kernels", "mesh"),
                    help=argparse.SUPPRESS)  # internal: child mode
    args = ap.parse_args()
    sys.path.insert(0, REPO)
    try:  # jax-free at import: safe for the parent
        from pushcdn_tpu.parallel.runtime import cpu_requested
    except ImportError as exc:
        print(f"chip_smoke: not in a checkout of the repo ({exc})",
              file=sys.stderr)
        return 2
    cpu = cpu_requested()
    if args.tiny and not cpu:
        print("chip_smoke: --tiny is the CPU dry run; set JAX_PLATFORMS=cpu",
              file=sys.stderr)
        return 2
    if cpu and not args.tiny:
        print("chip_smoke: JAX is held to the CPU (JAX_PLATFORMS=cpu) — no "
              "accelerator, no result", file=sys.stderr)
        return 2

    if args.leg:  # child: owns the device, reports on its last line
        try:
            report = leg_kernels(args.tiny) if args.leg == "kernels" \
                else asyncio.run(leg_mesh(args.tiny))
        except SmokeFailure as exc:
            say(f"{args.leg}: FAILED — {exc}")
            return 1
        print(json.dumps(report), flush=True)
        return 0 if report["ok"] else 1

    deadline = time.monotonic() + BUDGET_S
    procs = Procs()
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        from pushcdn_tpu import native
        t0 = time.monotonic()
        libs = native.build_all()
        say(f"native libraries ({time.monotonic() - t0:.1f}s): {libs}")
        check(all(libs.values()), f"native build failed: {libs}")

        kernels = run_leg_child("kernels", args.tiny, deadline, procs)
        device = kernels["device"]
        served = asyncio.run(leg_served(args.tiny, device, deadline, procs,
                                        workdir))
        if device["count"] >= 4:
            mesh = run_leg_child("mesh", args.tiny, deadline, procs)
            check(mesh["device"] == device, "mesh leg saw another device")
        else:
            say(f"mesh: not run — {device['count']} device(s) visible, "
                "the leg needs 4")
        check(served["device"] == device, "served leg saw another device")
        check("jax" not in sys.modules,
              "the smoke's parent imported jax (it would hold the chip)")
    except SmokeFailure as exc:
        say(f"FAILED — {exc} (logs under {workdir})")
        print("chip_smoke: FAILED", flush=True)
        return 1
    finally:
        procs.stop_all()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
