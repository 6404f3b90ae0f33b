"""Reconnect-storm resilience (ISSUE 12): full-jitter exponential
backoff, the typed ``Error(SHED)`` retry-after hint that floors it, and
the SQLite discovery store's bounded locked-write retry."""

import asyncio
import random
import sqlite3

import pytest

from pushcdn_tpu.client import client as client_mod
from pushcdn_tpu.client.client import Client, ClientConfig, backoff_delay
from pushcdn_tpu.proto.auth.user import _bail_rejection
from pushcdn_tpu.proto.crypto.signature import DEFAULT_SCHEME
from pushcdn_tpu.proto.discovery import embedded as emb
from pushcdn_tpu.proto.discovery.base import BrokerIdentifier
from pushcdn_tpu.proto.error import Error, ErrorKind, retry_after_hint
from pushcdn_tpu.proto.transport.memory import Memory
from pushcdn_tpu.testing.cluster import Cluster

# ---------------------------------------------------------------------------
# the backoff policy itself
# ---------------------------------------------------------------------------


def test_backoff_is_full_jitter():
    random.seed(1207)
    base, cap = 0.25, 30.0
    for attempt in range(12):
        ceiling = min(cap, base * (2 ** attempt))
        draws = [backoff_delay(attempt, base_s=base, cap_s=cap)
                 for _ in range(200)]
        assert all(0.0 <= d <= ceiling for d in draws)
        # FULL jitter: the whole [0, ceiling) range is drawn from — a
        # "equal jitter" or fixed-delay regression would never go low
        assert min(draws) < 0.2 * ceiling
        assert max(draws) > 0.8 * ceiling


def test_backoff_caps_growth():
    random.seed(7)
    for attempt in (20, 40, 63):
        assert backoff_delay(attempt, base_s=0.25, cap_s=3.0) <= 3.0


def test_backoff_retry_after_is_a_floor():
    random.seed(3)
    # attempt 0 draws from [0, 0.25); a 5 s server hint must dominate
    for _ in range(50):
        assert backoff_delay(0, retry_after_s=5.0) >= 5.0
    # ...but a hint SMALLER than the draw never truncates the jitter
    random.seed(3)
    draws = [backoff_delay(8, retry_after_s=0.001) for _ in range(50)]
    assert max(draws) > 1.0


# ---------------------------------------------------------------------------
# the typed hint, end to end
# ---------------------------------------------------------------------------


def test_retry_after_hint_parsing():
    assert retry_after_hint("shed: budget reached; retry-after=5") == 5.0
    assert retry_after_hint("shed: x; retry-after=2.75 more") == 2.75
    assert retry_after_hint("shed: no hint here") is None
    assert retry_after_hint("retry-after=abc") is None


def test_shed_error_carries_retry_after():
    e = Error(ErrorKind.SHED, "broker shed the connection: shed: user "
                              "connection budget 1 reached; retry-after=5")
    assert e.retry_after_s == 5.0
    # only SHED is a server pacing signal; other kinds never carry one
    e2 = Error(ErrorKind.AUTHENTICATION, "nope; retry-after=5")
    assert e2.retry_after_s is None


def test_bail_rejection_types_sheds():
    with pytest.raises(Error) as ei:
        _bail_rejection("broker", "shed: user connection budget 1 "
                                  "reached; retry-after=5")
    assert ei.value.kind == ErrorKind.SHED
    assert ei.value.retry_after_s == 5.0
    with pytest.raises(Error) as ei:
        _bail_rejection("marshal", "bad signature")
    assert ei.value.kind == ErrorKind.AUTHENTICATION


async def test_connect_shed_surfaces_typed_retry_after(monkeypatch):
    """A broker over its connection budget refuses at connect time with
    ``Error(SHED)`` carrying the readiness window as the retry hint —
    distinguishable from a real auth failure (which must NOT be paced)."""
    monkeypatch.setenv("PUSHCDN_MAX_CONNS_USER", "1")
    monkeypatch.setenv("PUSHCDN_SHED_READY_S", "3")
    cluster = await Cluster(num_brokers=1).start()
    try:
        first = cluster.client(seed=83_000)
        await asyncio.wait_for(first.ensure_initialized(), 10.0)
        second = cluster.client(seed=83_001)
        with pytest.raises(Error) as ei:
            await asyncio.wait_for(second._connect_once(), 10.0)
        assert ei.value.kind == ErrorKind.SHED
        assert ei.value.retry_after_s == 3.0
        first.close()
        second.close()
    finally:
        await cluster.stop()


async def test_reconnect_loop_uses_backoff(monkeypatch):
    """The reconnect loop feeds (attempt, server hint) into the policy —
    attempts count up, and the loop actually sleeps what it drew."""
    delays = []

    def fake_backoff(attempt, retry_after_s=None, **kw):
        delays.append((attempt, retry_after_s))
        return 0.0
    monkeypatch.setattr(client_mod, "backoff_delay", fake_backoff)
    c = Client(ClientConfig(
        marshal_endpoint="nowhere-no-listener",
        keypair=DEFAULT_SCHEME.generate_keypair(seed=83_002),
        protocol=Memory))
    task = asyncio.ensure_future(c._get_connection())
    while len(delays) < 4:
        await asyncio.sleep(0.01)
    task.cancel()
    with pytest.raises(asyncio.CancelledError):
        await task
    assert [a for a, _ in delays[:4]] == [0, 1, 2, 3]


# ---------------------------------------------------------------------------
# SQLite discovery: bounded retry past a write lock
# ---------------------------------------------------------------------------


def _identity(i=0):
    return BrokerIdentifier(f"lock-pub-{i}", f"lock-priv-{i}")


async def test_embedded_retries_past_held_write_lock(tmp_path, monkeypatch):
    """Another process holding BEGIN IMMEDIATE past busy_timeout makes
    every write raise 'database is locked'; the bounded retry schedule
    must ride it out once the lock releases."""
    monkeypatch.setattr(emb, "BUSY_TIMEOUT_MS", 25)
    monkeypatch.setattr(emb, "LOCKED_RETRY_SCHEDULE", (0.05, 0.1, 0.2))
    db = str(tmp_path / "d.sqlite")
    disc = await emb.Embedded.new(db, identity=_identity())
    locker = sqlite3.connect(db)
    try:
        locker.execute("BEGIN IMMEDIATE")  # hold the write lock

        async def release_soon():
            await asyncio.sleep(0.15)  # past busy_timeout + first retries
            locker.execute("COMMIT")

        releaser = asyncio.ensure_future(release_soon())
        await disc.perform_heartbeat(3, 60.0)  # must NOT raise
        await releaser
        others = await disc.get_other_brokers()
        assert others == []  # our own row landed (we are excluded)
    finally:
        locker.close()
        await disc.close()


async def test_embedded_lock_exhaustion_is_typed(tmp_path, monkeypatch):
    """A lock held past the WHOLE schedule surfaces as the typed
    Error(CONNECTION), never a raw sqlite3.OperationalError."""
    monkeypatch.setattr(emb, "BUSY_TIMEOUT_MS", 10)
    monkeypatch.setattr(emb, "LOCKED_RETRY_SCHEDULE", (0.02, 0.04))
    db = str(tmp_path / "d.sqlite")
    disc = await emb.Embedded.new(db, identity=_identity(1))
    locker = sqlite3.connect(db)
    try:
        locker.execute("BEGIN IMMEDIATE")
        with pytest.raises(Error) as ei:
            await disc.perform_heartbeat(1, 60.0)
        assert ei.value.kind == ErrorKind.CONNECTION
        assert "discovery store busy" in ei.value.message
        locker.execute("ROLLBACK")
    finally:
        locker.close()
        await disc.close()


_CONCURRENT_OPEN = """
import sys, time
from pushcdn_tpu.proto.discovery.embedded import Embedded
path, start = sys.argv[1], float(sys.argv[2])
while time.time() < start:
    pass
Embedded(path, None)
"""


def test_embedded_concurrent_first_open_across_processes(tmp_path):
    """A marshal and a broker opening one fresh store at the same moment
    (any multi-core host) both run the journal-mode switch, which sqlite
    fails with 'database is locked' WITHOUT consulting busy_timeout —
    the race behind the red test_two_process_kill_and_redeploy. More
    openers than cores, released together, several fresh files."""
    import os
    import subprocess
    import sys
    import time
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "PYTHONPATH": repo}
    openers = (os.cpu_count() or 2) + 2
    for rnd in range(3):
        path = str(tmp_path / f"d{rnd}.sqlite")
        start = time.time() + 1.0  # past every child's interpreter start
        procs = [subprocess.Popen(
            [sys.executable, "-c", _CONCURRENT_OPEN, path, str(start)],
            env=env, stderr=subprocess.PIPE, text=True)
            for _ in range(openers)]
        errors = []
        for p in procs:
            _, err = p.communicate(timeout=60)
            if p.returncode != 0:
                errors.append(err.strip().splitlines()[-1])
        assert not errors, errors


async def test_deregister_removes_broker_row(tmp_path):
    """Drain step 1: a deregistered broker leaves placement immediately
    and idempotently (every shard worker calls it)."""
    db = str(tmp_path / "d.sqlite")
    a = await emb.Embedded.new(db, identity=_identity(0))
    b = await emb.Embedded.new(db, identity=_identity(1))
    await a.perform_heartbeat(0, 60.0)
    await b.perform_heartbeat(5, 60.0)
    assert await a.get_with_least_connections() == _identity(0)
    await a.deregister()
    await a.deregister()  # idempotent
    assert await b.get_other_brokers() == []
    assert await b.get_with_least_connections() == _identity(1)
    await a.close()
    await b.close()
