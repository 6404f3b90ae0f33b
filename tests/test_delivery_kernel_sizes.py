"""The delivery kernel past the 1,024-row table (ISSUE 27): ``U = 1,088``
(17 x 64) and ``U = 5,056`` (79 x 64), which are no powers of two and
which no step runs at today, and ``U = 8,192``, what a 5,000-user
broker steps at (``DevicePlane._step_users``); at the served lanes ``N`` = 1,024
(tiles: Pallas, interpreted here), 64 and 8 (do not tile: the XLA twin
by ``selects_pallas`` on every backend). The dispatch the step calls, the
Pallas kernel itself and the jnp reference all against the delivery rule
in plain numpy, bit for bit."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chip_smoke import _np_delivery, _seeded_frames, _seeded_table  # noqa: E402
from pushcdn_tpu.parallel.frames import TOPIC_WORDS_FULL  # noqa: E402


@pytest.mark.parametrize("U,N", [
    (1088, 1024), (1088, 64), (1088, 8), (5056, 1024), (5056, 64),
    (5056, 8), (8192, 1024), (8192, 64)])
def test_kernel_matches_the_rule_at_grown_tables(U, N):
    import jax
    import jax.numpy as jnp

    from pushcdn_tpu.ops import delivery_kernel as dk

    W = TOPIC_WORDS_FULL
    rng = np.random.default_rng(U * 10_000 + N)
    masks, local = _seeded_table(rng, U, W)
    kind, tmask, dest = _seeded_frames(rng, N, U, W)
    # directs to the table's last rows, past every old capacity mark
    dest[:4] = (U - 1, U - 2, 1024, 1023)
    want = _np_delivery(masks, local, tmask, kind, dest)
    assert want.shape == (U, N) and want[1024:].any()
    args = tuple(jnp.asarray(a) for a in (masks, local, tmask, kind, dest))
    ref = np.asarray(jax.jit(dk.delivery_matrix_reference)(*args))
    np.testing.assert_array_equal(ref, want)
    # what the step calls with Pallas forced, as chip_smoke does off the
    # chip: the kernel where the shapes tile, the twin where they do not
    tiles = dk.selects_pallas(U, N, True)
    assert tiles == (N == 1024)
    got = np.asarray(jax.jit(
        lambda *a: dk.delivery_matrix(*a, use_pallas=True))(*args))
    assert got.dtype == np.bool_
    np.testing.assert_array_equal(got, want)
    if tiles:
        pal = np.asarray(dk.delivery_matrix_pallas(*args, interpret=True))
        np.testing.assert_array_equal(pal, want)
