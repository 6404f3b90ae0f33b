"""The pooled egress buffers of ``native.egress_encode``: a step's buffer
comes back for the steps after it, also when those are somewhat larger,
and a pool that filled up while the traffic was small does not turn the
larger buffers away."""

import gc

import numpy as np
import pytest

from pushcdn_tpu import native

MB = 1 << 20


@pytest.fixture
def pool():
    # leases an earlier test of this worker left in garbage come back to
    # the pool now, not at a ``gc.collect()`` inside the test
    gc.collect()
    saved, need = list(native._EGRESS_POOL), native._EGRESS_NEED_HW
    del native._EGRESS_POOL[:]
    native._EGRESS_NEED_HW = 64 * MB  # nothing below is "far above need"
    yield native._EGRESS_POOL
    del native._EGRESS_POOL[:]
    native._EGRESS_POOL.extend(saved)
    native._EGRESS_NEED_HW = need


@pytest.mark.parametrize("asked", [1, 3 * MB, 40 * MB])
def test_fresh_buffer_has_headroom(pool, asked):
    buf, lease = native._egress_take(asked)
    assert len(buf) >= max(asked + asked // 2, MB)
    del lease
    gc.collect()
    assert len(pool) == 1 and pool[0] is buf


@pytest.mark.parametrize("growth", [1.0, 1.2, 1.45])
def test_a_larger_step_reuses_the_buffer(pool, growth):
    first, lease = native._egress_take(10 * MB)
    del lease
    gc.collect()
    again, lease = native._egress_take(int(10 * MB * growth))
    assert again is first and not pool
    del lease
    gc.collect()


def test_a_step_past_the_headroom_allocates_and_both_are_kept(pool):
    first, lease = native._egress_take(4 * MB)
    del lease
    gc.collect()
    big, lease = native._egress_take(20 * MB)
    assert big is not first and len(big) >= 30 * MB
    del lease
    gc.collect()
    assert sorted(map(len, pool)) == [len(first), len(big)]


def test_a_full_pool_of_small_buffers_yields_to_a_larger_one(pool):
    small = [native._egress_take(1) for _ in range(native._EGRESS_POOL_MAX)]
    big, big_lease = native._egress_take(8 * MB)
    while small:
        small.pop()
        gc.collect()
    assert len(pool) == native._EGRESS_POOL_MAX
    del big_lease
    gc.collect()
    assert len(pool) == native._EGRESS_POOL_MAX
    assert any(b is big for b in pool)
    # and the next step of that size finds it
    again, lease = native._egress_take(8 * MB)
    assert again is big
    del lease
    gc.collect()


def test_a_full_pool_of_large_buffers_drops_a_smaller_one(pool):
    held = [native._egress_take(8 * MB) for _ in range(native._EGRESS_POOL_MAX)]
    tiny, tiny_lease = native._egress_take(1)
    # the pool was empty for every take above, so all four are distinct
    while held:
        held.pop()
        gc.collect()
    del tiny_lease
    gc.collect()
    assert len(pool) == native._EGRESS_POOL_MAX
    assert all(b is not tiny for b in pool)


def test_a_buffer_far_above_recent_need_is_not_pooled(pool):
    native._EGRESS_NEED_HW = MB
    _, lease = native._egress_take(16 * MB)
    del lease
    gc.collect()
    assert not pool


@pytest.mark.skipif(native._get() is None, reason="native library missing")
def test_encode_reuses_one_buffer_over_growing_steps(pool):
    """Steps whose size scatters upward by a third ride one allocation."""
    users, rows, width = 64, 32, 2048
    frames = np.full((rows, width), 7, np.uint8)
    lengths = np.full(rows, width, np.int32)
    seen = set()
    for live in (20, 24, 22, 28, 26):
        deliver = np.zeros((users, rows), np.bool_)
        deliver[:, :live] = True
        streams = native.egress_encode(deliver, lengths, [frames])
        assert streams.total_msgs == users * live
        assert int(streams.nbytes.sum()) == users * live * (width + 4)
        seen.add(id(streams.buf))
        del streams
        gc.collect()
    assert len(seen) == 1
