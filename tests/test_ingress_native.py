"""The user loop's native pass from a receive chunk to the device rings
(``DevicePlane.stage_chunk``, ``handlers._native_pass``) against what it
stands in for: the same batch through the scalar scan and one
``stage_batch``. Two brokers with a device plane that is never started
(no pump: the rings keep what the batch staged) take the same frames, one
as a ``FrameChunk`` with the pass, one as ``Bytes`` without; the rings'
columns byte for byte, every stage result in order, what each step took
while a full ring's retries waited, what the host routed, the plane's counters, the class counters
and the ingress ledger must agree. And the pass's own counters."""

import asyncio
import itertools
import os
import struct
import sys
import tempfile

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from pushcdn_tpu.broker.staging import StageResult  # noqa: E402
from pushcdn_tpu.proto.message import (  # noqa: E402
    Broadcast,
    Direct,
    Subscribe,
    TracedBroadcast,
    serialize,
)

_UNIQUE = itertools.count()
# user i subscribes to these; user 0 publishes
USER_TOPICS = ([0], [1], [0, 1], [40])
MIRRORED = b"user-1"


def _users(n=len(USER_TOPICS)):
    return [b"user-%d" % i for i in range(n)]


async def _broker(plane_kw: dict):
    """A broker whose device plane is never started, and its users
    (``USER_TOPICS``, over Memory pairs nobody reads: the host's part of
    the route is taken from the egress batch)."""
    from pushcdn_tpu.broker.broker import Broker, BrokerConfig
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from pushcdn_tpu.proto.crypto.signature import DEFAULT_SCHEME
    from pushcdn_tpu.proto.def_ import testing_run_def
    from pushcdn_tpu.proto.topic import TopicSpace
    from pushcdn_tpu.proto.transport.memory import gen_testing_connection_pair
    uid = next(_UNIQUE)
    db = os.path.join(tempfile.mkdtemp(prefix="pushcdn-native-"), "d.sqlite")
    cfg = dict(num_user_slots=32, ring_slots=16, frame_bytes=1024,
               extra_lanes=((4096, 4),), bypass_max_items=0)
    cfg.update(plane_kw)
    broker = await Broker.new(BrokerConfig(
        run_def=testing_run_def(topics=TopicSpace.range(64)),
        keypair=DEFAULT_SCHEME.generate_keypair(seed=4500 + uid),
        discovery_endpoint=db,
        public_advertise_endpoint=f"ni-{uid}-pub",
        public_bind_endpoint=f"ni-{uid}-pub",
        private_advertise_endpoint=f"ni-{uid}-priv",
        private_bind_endpoint=f"ni-{uid}-priv",
        heartbeat_interval_s=3600, sync_interval_s=3600,
        whitelist_interval_s=3600, device_plane=DevicePlaneConfig(**cfg)))
    for key, topics in zip(_users(), USER_TOPICS):
        local, _remote = await gen_testing_connection_pair(broker.limiter)
        broker.connections.add_user(key, local, topics)
    return broker


def _wire(frames) -> bytes:
    return b"".join(struct.pack(">I", len(f)) + f for f in frames)


def _chunk(wire: bytes):
    from pushcdn_tpu.proto.transport.base import FrameChunk, _py_scan_frames
    offs, lens, consumed, oversized = _py_scan_frames(wire, 1 << 20)
    assert consumed == len(wire) and not oversized
    return FrameChunk(wire, offs, lens, None)


def _bc(topics, size=100, tag=b"b"):
    return serialize(Broadcast(topics=topics, message=tag.ljust(size, b".")))


def _di(to, size=60, tag=b"d"):
    return serialize(Direct(recipient=to, message=tag.ljust(size, b".")))


def _seq(spec):
    """Frames from a compact spec, each payload unique (its index)."""
    out = []
    for i, (kind, arg, size) in enumerate(spec):
        tag = b"%s%d" % (kind.encode(), i)
        if kind == "b":
            out.append(_bc(arg, size, tag))
        elif kind == "d":
            out.append(_di(arg, size, tag))
        elif kind == "sub":
            out.append(serialize(Subscribe(arg)))
        elif kind == "traced":
            out.append(serialize(TracedBroadcast(
                arg, tag.ljust(size, b"."), (7, 9))))
        else:  # raw bytes
            out.append(arg)
    return out


KNOWN = [("b", [0], 100), ("b", [1], 600), ("b", [0, 1], 1500),
         ("d", MIRRORED, 60), ("b", [1, 0, 1], 900), ("b", [40], 30)]

CASES = {
    # every frame the pass can take, both lanes, directs among them
    "known_topics": ({}, [KNOWN]),
    # 100 is no topic of the deployment: the scan prunes it and stages
    # the frame with its bit; the pass stops there
    "unknown_topic": ({}, [KNOWN[:2] + [("b", [0, 100], 80)] + KNOWN[2:]]),
    # 40 is a topic, beyond a one-word mask: host-routed
    "out_of_range_topic": ({"topic_words": 1},
                           [KNOWN[:3] + [("b", [40], 80)] + KNOWN[:2]]),
    # no topic at all: the scan drops it
    "no_topic": ({}, [KNOWN[:2] + [("b", [], 80)] + KNOWN[2:]]),
    "directs_known_and_unknown": ({}, [
        [("d", MIRRORED, 60), ("d", b"nobody", 60), ("d", MIRRORED, 2000),
         ("b", [0], 100)]]),
    "wider_than_the_widest_lane": ({}, [
        KNOWN[:2] + [("b", [1], 5000)] + KNOWN[2:]]),
    "subscribe_mid_chunk": ({}, [KNOWN[:3] + [("sub", [1], 0)] + KNOWN[3:]]),
    # taken, and its ingress span emitted as the scan's route emits it
    "traced_mid_chunk": ({}, [KNOWN[:2] + [("traced", [0], 70)] + KNOWN]),
    "malformed_frame": ({}, [KNOWN[:3] + [("raw", b"\xfe\x00", 0)]
                             + KNOWN[3:]]),
    "ring_fills_mid_chunk": ({"ring_slots": 4, "extra_lanes": ((4096, 2),)},
                             [KNOWN + KNOWN]),
    # the stop in the first of two chunks: the second goes to the scan
    "stop_then_another_chunk": ({}, [KNOWN[:2] + [("sub", [0], 0)],
                                     KNOWN]),
    "two_whole_chunks": ({}, [KNOWN[:3], KNOWN[3:]]),
    "unmirrored_users": ({"unmirrored": True}, [
        [("d", MIRRORED, 60)] + KNOWN]),
    # idle plane: two frames are host-routed, three are staged
    "idle_bypass": ({"bypass_max_items": 2}, [KNOWN[:2]]),
    "idle_bypass_stop": ({"bypass_max_items": 2},
                         [KNOWN[:2] + [("sub", [1], 0)]]),
    "idle_over_the_bypass": ({"bypass_max_items": 2}, [KNOWN[:3]]),
}


def _rings(plane):
    return [(ring.slots - ring.free_slots,
             [np.array(col[:ring.slots - ring.free_slots])
              for col in ring.columns()]) for ring in plane.rings]


def _taken(plane):
    """What a step's take holds (and the rings emptied, as the pump's
    take leaves them), in the form of ``_rings``."""
    out = []
    for ring in plane.rings:
        used = ring.slots - ring.free_slots
        b = ring.take_batch()
        out.append((used, [np.array(col[:used]) for col in (
            b.bytes_, b.kind, b.length, b.topic_mask, b.dest, b.valid)]))
    return out


async def _run(monkeypatch, plane_kw, chunks, native: bool):
    """One batch of ``chunks`` (lists of wire frames) through the user
    loop's batch routine: everything the comparison reads."""
    from pushcdn_tpu.broker.tasks import handlers
    from pushcdn_tpu.proto import ledger as ledger_mod
    from pushcdn_tpu.proto import metrics as metrics_mod
    plane_kw = dict(plane_kw)
    unmirrored = plane_kw.pop("unmirrored", False)
    broker = await _broker(plane_kw)
    plane = broker.device_plane
    if unmirrored:
        plane._unmirrored.add(b"ghost")
    results, takes, routed = [], [], []
    real_batch, real_chunk = plane.stage_batch, plane.stage_chunk

    def stage_batch(items):
        out = real_batch(items)
        results.extend(zip((bytes(r.data) for _, r in items), out))
        return out

    def stage_chunk(buf, offs, lens, first, retry=False):
        taken, status, counts = real_chunk(buf, offs, lens, first, retry)
        for j in range(taken if not retry else 0):
            o = offs[first + j]
            results.append((bytes(buf[o:o + lens[first + j]]),
                            StageResult.STAGED if status[j] & 1
                            else StageResult.FULL))
        return taken, status, counts
    plane.stage_batch, plane.stage_chunk = stage_batch, stage_chunk

    class Pump:
        """The module's ``asyncio`` with a pump in its ``sleep``: a retry
        that found the ring full waits, and meanwhile a step takes."""

        def __getattr__(self, name):
            return getattr(asyncio, name)

        async def sleep(self, _seconds):
            takes.append(_taken(plane))
            await asyncio.sleep(0)
    monkeypatch.setattr(handlers, "asyncio", Pump())

    async def flush(egress):
        routed.append({key: [bytes(f.data) for f in frames]
                       for key, frames in egress.users.items()})
        egress.release_all()
    monkeypatch.setattr(handlers.EgressBatch, "flush", flush)
    spans = []
    monkeypatch.setattr(handlers.trace_mod, "emit",
                        lambda hop, tr, note: spans.append((hop, tr, note)))

    classes = [(c.value, b.value) for c, b in zip(
        metrics_mod.CLASS_FRAMES_IN, metrics_mod.CLASS_BYTES_IN)]
    ingress = list(ledger_mod.LEDGER.ingress)
    wires = [_wire(_seq(spec)) for spec in chunks]
    items = ([_chunk(w) for w in wires] if native else
             [c.take() for w in wires for c in [_chunk(w)]
              for _ in range(c.remaining)])
    conn = broker.connections.get_user_connection(_users()[0])
    try:
        assert handlers._takes_chunks(broker, plane,
                                      broker.run_def.user_def.hook)
        alive = await handlers._route_user_batch(
            broker, _users()[0], conn, broker.run_def.user_def.hook,
            broker.run_def.topics, items, native)
        out = {
            "alive": alive, "results": results, "takes": takes,
            "spans": spans,
            "routed": routed, "rings": _rings(plane),
            "counters": (plane.frames_staged, plane.stage_full_frames,
                         plane.stage_full_results),
            "classes": [(c.value - c0, b.value - b0) for (c0, b0), c, b in
                        zip(classes, metrics_mod.CLASS_FRAMES_IN,
                            metrics_mod.CLASS_BYTES_IN)],
            "ingress": [a - b for a, b in zip(ledger_mod.LEDGER.ingress,
                                              ingress)],
            "subscribed": sorted(broker.connections.user_topics
                                 .get_values_of_key(_users()[0])),
        }
        native_counts = (plane.ingress_native_frames,
                         plane.ingress_native_stops,
                         plane.ingress_native_restaged)
    finally:
        await broker.stop()
    return out, native_counts


def _assert_same(a: dict, b: dict) -> None:
    assert a["alive"] == b["alive"]
    assert a["results"] == b["results"]
    assert len(a["takes"]) == len(b["takes"])
    for take_a, take_b in zip(a["takes"], b["takes"]):
        _assert_same_rings(take_a, take_b)
    assert a["spans"] == b["spans"]
    assert a["routed"] == b["routed"]
    assert a["counters"] == b["counters"]
    assert a["classes"] == b["classes"]
    assert a["ingress"] == b["ingress"]
    assert a["subscribed"] == b["subscribed"]
    _assert_same_rings(a["rings"], b["rings"])


def _assert_same_rings(a: list, b: list) -> None:
    assert len(a) == len(b)
    for (used_a, cols_a), (used_b, cols_b) in zip(a, b):
        assert used_a == used_b
        for col_a, col_b in zip(cols_a, cols_b):
            assert col_a.dtype == col_b.dtype
            assert col_a.tobytes() == col_b.tobytes()


@pytest.mark.parametrize("case", list(CASES))
async def test_the_native_pass_stages_what_the_scan_and_stage_batch_stage(
        case, monkeypatch):
    plane_kw, chunks = CASES[case]
    scalar, untouched = await _run(monkeypatch, plane_kw, chunks, False)
    native, (frames, stops, restaged) = await _run(monkeypatch, plane_kw,
                                                   chunks, True)
    _assert_same(native, scalar)
    assert untouched == (0, 0, 0)
    staged = sum(r == StageResult.STAGED for _, r in native["results"])
    assert 0 <= frames <= staged
    if case != "ring_fills_mid_chunk":
        assert restaged == 0
    taken_all = case in ("known_topics", "two_whole_chunks",
                         "idle_over_the_bypass", "ring_fills_mid_chunk",
                         "traced_mid_chunk")
    if taken_all:
        assert (frames, stops) == (staged, 0) and frames > 0
    elif case == "idle_bypass":   # the whole batch is the bypass's
        assert (frames, stops) == (0, 0)
    else:
        assert stops == 1, (frames, stops)
    if case == "ring_fills_mid_chunk":
        assert native["takes"] and restaged > 0
        assert frames + restaged == native["counters"][0]
    if case == "traced_mid_chunk":
        assert native["spans"] == [("ingress", (7, 9), "device-staged")]
    if case == "malformed_frame":
        assert native["alive"] is False


async def test_the_pass_counters_bound_what_they_count():
    """Over real TCP links into a running plane whose base lane fills:
    ``ingress_native_frames <= frames_staged`` and
    ``ingress_native_restaged <= stage_full_frames``, the retries staged
    what was held back, and every subscriber got every frame."""
    import asyncio

    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from pushcdn_tpu.testing import wait_until
    from tests.test_device_plane import _served_over_tcp, _socket_of, _wire

    async with _served_over_tcp(
            4520, DevicePlaneConfig(num_user_slots=32, ring_slots=16,
                                    frame_bytes=1024, extra_lanes=(),
                                    batch_window_s=0.002),
            [{0}, {0}, {1}]) as (broker, clients):
        plane = broker.device_plane
        got = [0] * 3

        async def read(u):
            while True:
                got[u] += len(await clients[u].receive_messages())
        readers = [asyncio.create_task(read(u)) for u in range(3)]
        try:
            sock = _socket_of(clients[0])
            for r in range(8):
                os.write(sock, _wire(*[b"%d-%d" % (r, i) for i in range(40)])
                         + _wire(b"to 2", to=clients[2].public_key))
            await wait_until(lambda: got == [320, 320, 8], timeout=30)
        finally:
            for t in readers:
                t.cancel()
        d = plane.describe()
    assert 0 < d["ingress_native_frames"] <= d["frames_staged"] == 328
    assert 0 < d["ingress_native_restaged"] <= d["stage_full_frames"]
    assert d["ingress_native_stops"] == 0


async def test_the_pass_counters_stand_still_where_it_never_engages(
        monkeypatch):
    """A message hook of the deployment's own: the scalar scan runs it on
    every frame, so the pass never engages and its counters read 0 while
    the plane stages."""
    import asyncio

    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from pushcdn_tpu.broker.tasks import handlers
    from pushcdn_tpu.proto import def_
    from pushcdn_tpu.proto.def_ import HookResult
    from pushcdn_tpu.testing import wait_until
    from tests.test_device_plane import _served_over_tcp, _socket_of, _wire

    seen = []

    def hook(sender, message):
        seen.append(sender)
        return HookResult.PROCESS
    real = def_.testing_run_def

    def hooked(**kw):
        run_def = real(**kw)
        run_def.user_def.hook = hook
        return run_def
    monkeypatch.setattr(def_, "testing_run_def", hooked)
    async with _served_over_tcp(
            4530, DevicePlaneConfig(num_user_slots=32, ring_slots=64,
                                    frame_bytes=1024, batch_window_s=0.002,
                                    bypass_max_items=0),
            [{0}, {0}]) as (broker, clients):
        plane = broker.device_plane
        assert broker.run_def.user_def.hook is hook
        assert not handlers._takes_chunks(broker, plane, hook)
        got = [0, 0]

        async def read(u):
            while True:
                got[u] += len(await clients[u].receive_messages())
        readers = [asyncio.create_task(read(u)) for u in range(2)]
        try:
            os.write(_socket_of(clients[0]),
                     _wire(*[b"x%d" % i for i in range(20)]))
            await wait_until(lambda: got == [20, 20], timeout=30)
        finally:
            for t in readers:
                t.cancel()
        d = plane.describe()
    assert d["frames_staged"] == 20 and len(seen) >= 20
    assert (d["ingress_native_frames"], d["ingress_native_stops"],
            d["ingress_native_restaged"]) == (0, 0, 0)
