"""A back-pressured step whose one user's stream is longer than the flush
unit (ISSUE 41, the skewed cell ``broker1-1k.zipf-sat``): that stream
goes to the user's writer task while the rest leave in the native batch,
the next steps' streams of that user queue behind it in order, the
step's pooled buffer stays out of the pool while the writer holds it,
and the program counts all of it (``egress_oversize``, ``plane.egress``'s
``oversize``, ``egress_pool_*``)."""

import gc
import os
import socket

import pytest

from benchmark import reference
from benchmark.loadgen.plan import BROADCAST
from pushcdn_tpu import native
from pushcdn_tpu.proto.transport.base import Connection
from tests.test_device_plane import (
    _receive_all,
    _served_over_tcp,
    _socket_of,
    _wire,
)
from tests.test_integration import wait_until
from tests.test_plane_spans import _program_spans

_LANE = 112
_PLANE = dict(num_user_slots=32, ring_slots=_LANE, frame_bytes=1024,
              batch_window_s=0.002, bypass_max_items=0)
# the publisher, the hot subscriber (both topics) and two on topic 1 alone
_TOPICS = [{1}, {0, 1}, {1}, {1}]
_HOT = 1


def _round(r: int, hot: int) -> list:
    """One take's frames, ``(topic, payload)``: ``hot`` of 900 B on topic
    0, the rest of the lane 50 B on topic 1, interleaved."""
    frames, seq = [], {0: 0, 1: 0}
    for i in range(_LANE):
        topic = 0 if i * hot // _LANE != (i + 1) * hot // _LANE else 1
        frames.append((topic, (b"%d|%d|%d|" % (r, topic, seq[topic])).ljust(
            900 if topic == 0 else 50, b".")))
        seq[topic] += 1
    assert sum(t == 0 for t, _ in frames) == hot
    return frames


@pytest.fixture
def pool():
    gc.collect()  # leases an earlier test left in garbage come back now
    saved = list(native._EGRESS_POOL)
    del native._EGRESS_POOL[:]
    yield native._EGRESS_POOL
    del native._EGRESS_POOL[:]
    native._EGRESS_POOL.extend(saved)


async def test_an_oversize_stream_queues_in_order_and_pins_its_buffer(
        pool, monkeypatch, tmp_path):
    import jax

    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from pushcdn_tpu.parallel import spans
    spans.bind()
    taken = []  # each step's egress buffer, never its lease
    real_encode = native.egress_encode

    def encode(*args, **kwargs):
        streams = real_encode(*args, **kwargs)
        taken.append(streams.buf)
        return streams
    monkeypatch.setattr(native, "egress_encode", encode)

    def pooled(buf) -> bool:
        return any(b is buf for b in pool)

    # round 0: 100 frames of 900 B for the hot user (~92 KB, over the
    # unit); rounds 1-3: 8 (~12 KB with the small ones, under it)
    rounds = [_round(0, 100)] + [_round(r, 8) for r in (1, 2, 3)]
    log = []
    async with _served_over_tcp(4101, DevicePlaneConfig(**_PLANE),
                                _TOPICS) as (broker, clients):
        plane = broker.device_plane
        hot = clients[_HOT]
        link = broker.connections.get_user_connection(hot.public_key)
        # a reader that stops: the writer's flush of the hot stream waits
        hot._connection._stream.writer.get_extra_info("socket").setsockopt(
            socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        link._stream.writer.get_extra_info("socket").setsockopt(
            socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        link._stream.writer.transport.set_write_buffer_limits(high=16384)
        hot._connection._stream.reader._transport.pause_reading()
        counted0 = native.egress_pool_counters()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            for r, frames in enumerate(rounds[:3]):
                wire = b"".join(_wire(p, topic=t) for t, p in frames)
                assert os.write(_socket_of(clients[0]), wire) == len(wire)
                log += [(0, BROADCAST, t) for t, _ in frames]
                await wait_until(lambda: plane.steps == r + 1
                                 and not plane._step_inflight)
                cold = [p for t, p in frames if t == 1]
                got = await _receive_all([clients[i] for i in (0, 2, 3)],
                                         len(cold))
                assert got == [cold] * 3
                # the hot user's writer still holds round 0's stream, so
                # that step's buffer is out of the pool
                assert link._write_mutex.locked()
                assert not pooled(taken[0])
        finally:
            jax.profiler.stop_trace()
        assert (plane.egress_oversize, plane.egress_queued,
                plane.egress_batched, plane.egress_inline) == (1, 3, 9, 9)
        assert plane.egress_oversize_bytes > Connection._BATCH_COALESCE_LIMIT
        described = plane.describe()
        assert (described["egress_oversize"],
                described["egress_oversize_bytes"]) == (
                    1, plane.egress_oversize_bytes)
        # three takes, each on an empty pool: three fresh buffers
        counted = native.egress_pool_counters()
        moved = {k: counted[k] - counted0[k] for k in counted}
        assert moved == {"egress_pool_takes": 3, "egress_pool_fresh": 3,
                         "egress_pool_fresh_bytes": sum(map(len, taken))}
        assert all(len(b) >= 1 << 20 for b in taken)
        assert {k: described[k] for k in counted} == counted

        # the reader reads again: the writer flushes rounds 0-2 in order,
        # and round 0's buffer returns to the pool once its flush is done
        hot._connection._stream.reader._transport.resume_reading()
        owed_hot = sum(len(frames) for frames in rounds[:3])
        got_hot, = await _receive_all([hot], owed_hot)
        assert len(got_hot) == owed_hot
        await wait_until(lambda: not link._write_mutex.locked()
                         and pooled(taken[0]))
        # the fourth step takes a pooled buffer: a take, nothing fresh;
        # the idle hot link is batched again
        frames = rounds[3]
        wire = b"".join(_wire(p, topic=t) for t, p in frames)
        assert os.write(_socket_of(clients[0]), wire) == len(wire)
        log += [(0, BROADCAST, t) for t, _ in frames]
        got = await _receive_all([clients[i] for i in (0, 2, 3)],
                                 sum(t == 1 for t, _ in frames))
        assert got == [[p for t, p in frames if t == 1]] * 3
        got_hot += (await _receive_all([hot], len(frames)))[0]
        moved = {k: native.egress_pool_counters()[k] - counted[k]
                 for k in counted}
        assert moved["egress_pool_takes"] == 1
        assert moved["egress_pool_fresh"] == 0
        assert moved["egress_pool_fresh_bytes"] == 0
        assert (plane.egress_oversize, plane.egress_queued,
                plane.egress_batched) == (1, 3, 13)
        assert broker.connections.num_users == len(clients)
        assert not plane.disabled

    # FIFO per (publisher, topic): what the hot user got, stream by
    # stream, is 0..n-1 of what the plain reference owes it
    owed = reference.route(_TOPICS, log)[_HOT]
    seen = {}
    for payload in got_hot:
        r, topic, _ = payload.split(b"|", 3)[:3]
        seen.setdefault(int(topic), []).append(payload)
    order = {t: [p for frames in rounds for tt, p in frames if tt == t]
             for t in (0, 1)}
    assert {(0, t): len(ps) for t, ps in seen.items()} == owed
    assert seen == order

    # the span says it too: Σ ``oversize`` over ``plane.egress`` is the
    # counter's, all of it in the first step
    threads, _ = _program_spans(str(tmp_path))
    egresses = sorted((e for evs in threads.values() for e in evs
                       if e[0] == "plane.egress"), key=lambda e: e[1])
    assert [(g[3]["inline"], g[3]["queued"], g[3]["batched"],
             g[3]["oversize"]) for g in egresses] == [
                 (3, 1, 3, 1), (3, 1, 3, 0), (3, 1, 3, 0)]


@pytest.mark.parametrize("deploy", ["device_plane", "mesh_group"])
async def test_streams_under_the_unit_count_nothing_and_both_planes_say_so(
        deploy):
    """A back-pressured step (tick) whose streams all fit the unit, as
    every step of ``fanout4-sat`` does: no oversize hand-off, every one
    batched, and both planes' ``describe()`` carry the five counters, the
    pool's as the process keeps them."""
    from pushcdn_tpu.broker.device_plane import DevicePlaneConfig
    from tests.test_device_plane import _SMALL_PLANE
    from tests.test_mesh_group import _RING, _served_group
    if deploy == "device_plane":
        lane, users = _SMALL_PLANE["ring_slots"], 2
        served = _served_over_tcp(
            4102, DevicePlaneConfig(bypass_max_items=0, **_SMALL_PLANE),
            [{0}] * users)
    else:
        lane, users = _RING, 4      # one a shard
        served = _served_group(per_shard=1)
    async with served as (serving, clients):
        if deploy == "device_plane":
            plane = facade = serving.device_plane
        else:
            plane, facade = serving.group, serving.brokers[0].device_plane
        before = native.egress_pool_counters()
        for r in range(2):
            frames = [(b"%d.%d|" % (r, i)).ljust(600, b".")
                      for i in range(lane)]
            os.write(_socket_of(clients[0]), _wire(*frames))
            assert await _receive_all(clients, lane) == [frames] * users
        described = facade.describe()
    assert plane.egress_batched == 2 * users
    assert (plane.egress_oversize, plane.egress_oversize_bytes) == (0, 0)
    assert (described["egress_oversize"],
            described["egress_oversize_bytes"]) == (0, 0)
    counted = native.egress_pool_counters()
    assert {k: described[k] for k in counted} == counted
    assert counted["egress_pool_takes"] - before["egress_pool_takes"] >= 2
    assert counted["egress_pool_fresh"] <= counted["egress_pool_takes"]
