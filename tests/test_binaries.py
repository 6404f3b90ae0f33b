"""Binary smoke tier: the production CLIs must actually wire the flags
they advertise. Runs the broker binary WITH --device-plane as a real OS
process over TCP, authenticates a client through the marshal binary, and
proves a burst routed on-device by scraping the broker's /metrics
endpoint (cdn_device_messages_routed > 0) — CLI → plane → metrics, full
circle. (The reference's process-compose tier is scripts/local_cluster.py;
this is the always-on pytest slice of it.)"""

import asyncio
import os
import subprocess
import sys
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn(name: str, *args: str) -> subprocess.Popen:
    from pushcdn_tpu.bin.common import spawn_binary
    return spawn_binary(name, *args,
                        env_extra={"JAX_PLATFORMS":
                                   os.environ.get("JAX_PLATFORMS", "cpu")})


async def test_broker_binary_device_plane_end_to_end(tmp_path):
    db = str(tmp_path / "cdn.sqlite")
    from pushcdn_tpu.bin.common import free_ports
    pub, priv, metrics, marshal_p = free_ports(4)
    procs = []
    try:
        procs.append(_spawn(
            "broker", "--discovery-endpoint", db,
            "--public-advertise-endpoint", f"127.0.0.1:{pub}",
            "--public-bind-endpoint", f"127.0.0.1:{pub}",
            "--private-advertise-endpoint", f"127.0.0.1:{priv}",
            "--private-bind-endpoint", f"127.0.0.1:{priv}",
            "--metrics-bind-endpoint", f"127.0.0.1:{metrics}",
            "--user-transport", "tcp", "--device-plane",
            "--device-ring-slots", "64"))
        procs.append(_spawn(
            "marshal", "--discovery-endpoint", db,
            "--bind-endpoint", f"127.0.0.1:{marshal_p}",
            "--user-transport", "tcp"))

        from pushcdn_tpu.client import Client, ClientConfig
        from pushcdn_tpu.proto.crypto.signature import DEFAULT_SCHEME
        from pushcdn_tpu.proto.transport import Tcp

        client = Client(ClientConfig(
            marshal_endpoint=f"127.0.0.1:{marshal_p}",
            keypair=DEFAULT_SCHEME.generate_keypair(seed=4242),
            protocol=Tcp, subscribed_topics={0}))
        async with asyncio.timeout(45):  # binaries cold-start + register
            await client.ensure_initialized()

        # a pipelined burst beats the idle bypass and rides the device
        # (budgets stay under conftest's 120 s whole-test cap)
        for _ in range(3):
            await asyncio.gather(*(
                client.send_broadcast_message([0], b"cli burst %d" % i)
                for i in range(16)))
            got = 0
            # generous: under full-suite load on a single core the CLI
            # broker's first staged step can contend with other tests'
            # processes (observed flake at 15 s)
            async with asyncio.timeout(40):
                while got < 16:
                    got += len(await client.receive_messages(16 - got))
            text = await asyncio.to_thread(
                lambda: urllib.request.urlopen(
                    f"http://127.0.0.1:{metrics}/metrics",
                    timeout=5).read().decode())
            routed = [l for l in text.splitlines()
                      if l.startswith("cdn_device_messages_routed ")]
            if routed and float(routed[0].split()[-1]) > 0:
                break
        else:
            raise AssertionError(
                f"device plane never routed via the CLI broker:\n{text}")
        client.close()
        for p in procs:
            assert p.poll() is None, "a binary died during the test"
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def test_every_binary_parses_help():
    """All six CLIs must at least import and build their parsers — the
    load binaries (bad_*) have no other automated exercise as modules."""
    for name in ("broker", "marshal", "client",
                 "bad_broker", "bad_connector", "bad_sender"):
        p = _spawn(name, "--help")
        out, _ = p.communicate(timeout=60)
        assert p.returncode == 0, f"{name} --help failed:\n{out}"
        assert "usage" in out.lower(), out[:200]


def test_servers_raise_their_soft_fd_limit_to_the_hard_one():
    """``bin/broker`` and ``bin/marshal`` call ``raise_nofile_limit`` at
    start (ISSUE 27): a child started under a soft limit of 256 ends with
    soft == hard and says both in its log."""
    code = (
        "import logging, resource, sys\n"
        "logging.basicConfig(level=logging.INFO, stream=sys.stdout)\n"
        "soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)\n"
        "resource.setrlimit(resource.RLIMIT_NOFILE, (256, hard))\n"
        "from pushcdn_tpu.bin.common import raise_nofile_limit\n"
        "raise_nofile_limit()\n"
        "now = resource.getrlimit(resource.RLIMIT_NOFILE)\n"
        "print('LIMITS', now[0] == now[1] == hard, hard)\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=60, cwd=REPO, env={**os.environ, "PYTHONPATH": REPO})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "LIMITS True" in proc.stdout, proc.stdout
    hard = proc.stdout.split("LIMITS True ")[1].split()[0]
    assert f"RLIMIT_NOFILE: soft 256 -> {hard} (hard {hard})" in proc.stdout
    for name in ("broker", "marshal"):
        with open(os.path.join(REPO, "pushcdn_tpu", "bin", f"{name}.py")) as f:
            assert "    raise_nofile_limit()\n" in f.read(), name
