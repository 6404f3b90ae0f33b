"""Growth through the real binaries (ISSUE 27): ``bin/marshal`` and
``bin/broker --device-plane`` on the CPU, 1,100 idle TCP subscribers from
``testing/clientpack`` processes and a few dozen broadcasts and directs
(the shape of ``chip_smoke.py --tiny``'s served leg, light on traffic).
The 1,025th connection doubles the user table: ``/debug/topology`` reads
``user_slots`` 2,048, nobody is unmirrored, the grown table's two step
programs are compiled before any traffic and none after, every subscriber
counts what the subscription table owes it, and the device delivered
fan-out x frames staged."""

import asyncio
import json
import os
import resource
import subprocess
import sys
import tempfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chip_smoke import Pack, Procs, _http, _metric  # noqa: E402

PACKS, PER_PACK, TOPICS = 4, 275, 2       # 1,100 subscribers
SEED_BASE, PUBLISHER_SEED = 270_000, 279_999
BURST, ROUNDS, DIRECTS = 16, 2, 8
NEEDS_FDS = 4096  # 1,101 sockets in the broker, 275 a pack, with room


# an explicit CPU run of the binaries, one device, no compile cache
BIN_ENV = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
           "JAX_COMPILATION_CACHE_DIR": ""}


def _env():
    return {**os.environ, **BIN_ENV, "PYTHONPATH": REPO}


async def _serve(workdir, procs):
    from pushcdn_tpu.bin.common import free_ports, spawn_binary
    from pushcdn_tpu.client import Client, ClientConfig
    from pushcdn_tpu.proto.crypto.signature import DEFAULT_SCHEME
    from pushcdn_tpu.proto.transport import Tcp

    db = os.path.join(workdir, "discovery.sqlite")
    pub, priv, metrics, marshal_port = free_ports(4)
    broker = procs.add("broker", spawn_binary(
        "broker", "--discovery-endpoint", db,
        "--public-advertise-endpoint", f"127.0.0.1:{pub}",
        "--public-bind-endpoint", f"127.0.0.1:{pub}",
        "--private-advertise-endpoint", f"127.0.0.1:{priv}",
        "--private-bind-endpoint", f"127.0.0.1:{priv}",
        "--metrics-bind-endpoint", f"127.0.0.1:{metrics}",
        "--user-transport", "tcp", "--device-plane",
        env_extra=BIN_ENV, log_path=os.path.join(workdir, "broker.log")))

    async def topology():
        status, body = await asyncio.to_thread(_http, metrics,
                                               "/debug/topology")
        return json.loads(body) if status == 200 else None

    async def scrape():
        status, text = await asyncio.to_thread(_http, metrics, "/metrics")
        assert status == 200
        return {name: _metric(text, f"cdn_device_{name}") for name in (
            "frames_staged", "messages_routed", "steps", "user_slots",
            "plane_disabled")}

    async with asyncio.timeout(120):  # warm-up: compile and first step
        while True:
            assert broker.poll() is None, "the broker exited during start-up"
            topo = await topology()
            if topo and topo["device_plane"] and \
                    topo["device_plane"]["warmup_s"] is not None:
                break
            await asyncio.sleep(0.3)
    before = topo["device_plane"]
    assert (before["user_slots"], before["table_grows"]) == (1024, 0)
    assert before["kernels"]["1024x2048B"] == "xla"   # a CPU run
    with open(os.path.join(workdir, "broker.log")) as f:
        assert "RLIMIT_NOFILE: soft" in f.read()

    procs.add("marshal", spawn_binary(
        "marshal", "--discovery-endpoint", db,
        "--bind-endpoint", f"127.0.0.1:{marshal_port}",
        "--user-transport", "tcp", env_extra=BIN_ENV,
        log_path=os.path.join(workdir, "marshal.log")))
    publisher = Client(ClientConfig(
        marshal_endpoint=f"127.0.0.1:{marshal_port}",
        keypair=DEFAULT_SCHEME.generate_keypair(seed=PUBLISHER_SEED),
        protocol=Tcp))
    packs = []
    try:
        async with asyncio.timeout(60):
            await publisher.ensure_initialized()
        for p in range(PACKS):
            with open(os.path.join(workdir, f"pack{p}.log"), "ab") as errlog:
                packs.append(Pack(await asyncio.create_subprocess_exec(
                    sys.executable, "-m", "pushcdn_tpu.testing.clientpack",
                    "--marshal-endpoint", f"127.0.0.1:{marshal_port}",
                    "--clients", str(PER_PACK), "--topics", str(TOPICS),
                    "--seed-base", str(SEED_BASE + p * PER_PACK),
                    "--report-every-s", "3600", "--settle-s", "0.5",
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    stderr=errlog, env=_env())))
        for pack in packs:
            await pack.expect("ready", 150)

        # 1,101 users on a table that started at 1,024: it doubled once
        plane = (await topology())["device_plane"]
        assert plane["mirrored_users"] == PACKS * PER_PACK + 1
        assert plane["unmirrored_users"] == 0
        assert (plane["user_slots"], plane["table_grows"]) == (2048, 1)
        assert plane["user_high_water"] == PACKS * PER_PACK + 1
        assert (await scrape())["user_slots"] == 2048
        # 1,101 users step at 2,048 rows, and the pump loaded those two
        # programs when the table grew, before any traffic: none compiles
        # later
        async with asyncio.timeout(90):
            while (await topology())["device_plane"]["programs"] \
                    < before["programs"] + 2:
                await asyncio.sleep(0.2)

        # the subscription table: pack p's client i holds key seed
        # SEED_BASE + p * PER_PACK + i and the topic i % TOPICS
        topic_of = [[i % TOPICS for i in range(PER_PACK)]
                    for p in range(PACKS)]
        fanout = [sum(row.count(t) for row in topic_of)
                  for t in range(TOPICS)]
        expect = [[0] * PER_PACK for _ in range(PACKS)]
        next_seq = [0] * TOPICS
        owed_total = 0

        def payload(seq, nbytes):
            return seq.to_bytes(4, "big") + b"g" * (nbytes - 4)

        async def delivered(total):
            async with asyncio.timeout(90):
                while sum([(await pk.command("mark", "mark"))["unique"]
                           for pk in packs]) < total:
                    await asyncio.sleep(0.2)

        async def burst(send, fan):
            """One pipelined burst, held to the device's own counters:
            whatever the idle bypass host-routed, the device delivered
            ``fan`` x what it staged."""
            nonlocal owed_total
            c0 = await scrape()
            frames, deliveries = await send()
            owed_total += deliveries
            await delivered(owed_total)
            c1 = await scrape()
            staged = c1["frames_staged"] - c0["frames_staged"]
            assert 0 < staged <= frames
            assert c1["messages_routed"] - c0["messages_routed"] == \
                fan * staged
            return frames, staged

        def topic_burst(t):
            async def send():
                for _ in range(BURST):  # back to back: one write
                    await publisher.send_broadcast_message(
                        [t], payload(next_seq[t], 1000))
                    next_seq[t] += 1
                for p in range(PACKS):
                    for i in range(PER_PACK):
                        expect[p][i] += BURST * (topic_of[p][i] == t)
                return BURST, BURST * fanout[t]
            return send

        async def directs():
            # to distinct subscribers, after the last broadcast: each
            # continues its recipient's own topic sequence
            for k in range(DIRECTS):
                i = PER_PACK - 1 - k
                key = DEFAULT_SCHEME.generate_keypair(
                    seed=SEED_BASE + (PACKS - 1) * PER_PACK + i).public_key
                await publisher.send_direct_message(key, payload(
                    next_seq[topic_of[PACKS - 1][i]], 256))
                expect[PACKS - 1][i] += 1
            return DIRECTS, DIRECTS

        sent = staged = 0
        for _ in range(ROUNDS):
            for t in range(TOPICS):
                frames, on_device = await burst(topic_burst(t), fanout[t])
                sent, staged = sent + frames, staged + on_device
        frames, on_device = await burst(directs, 1)
        sent, staged = sent + frames, staged + on_device
        assert staged * 10 >= sent * 9   # the device carried the traffic

        results = [await pk.command("finish", "result", 60) for pk in packs]
        for p, res in enumerate(results):
            assert res["gaps"] == 0 and res["hard_reconnects"] == 0, (p, res)
            assert res["unique_by_client"] == expect[p], p
        for pk in packs:
            await pk.proc.wait()
        final = await scrape()
        assert final["plane_disabled"] == 0 and final["steps"] > 0
        plane = (await topology())["device_plane"]
        # users gone, the table keeps its size
        assert (plane["user_slots"], plane["table_grows"]) == (2048, 1)
        assert plane["programs"] == before["programs"] + 2
        rc = await asyncio.to_thread(Procs.stop, "broker", broker)
        assert rc == 0, f"the broker exited {rc} on SIGTERM"
    finally:
        publisher.close()
        for pk in packs:
            pk.reader.cancel()
            if pk.proc.returncode is None:
                pk.proc.terminate()
                try:
                    await asyncio.wait_for(pk.proc.wait(), 20)
                except asyncio.TimeoutError:
                    pk.proc.kill()
                    await pk.proc.wait()


def test_the_1025th_connection_grows_the_table_through_the_real_binaries():
    _soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if hard != resource.RLIM_INFINITY and hard < NEEDS_FDS:
        pytest.skip(f"the hard RLIMIT_NOFILE is {hard}, under {NEEDS_FDS}: "
                    "1,101 sockets in one broker do not fit")
    procs = Procs()
    workdir = tempfile.mkdtemp(prefix="pushcdn-served-growth-")
    try:
        asyncio.run(asyncio.wait_for(_serve(workdir, procs), 420))
    finally:
        procs.stop_all()
