"""Fused delivery-matrix kernel: the router's inner loop as one Pallas op.

Computes, for every (local user u, gathered frame n):

    deliver[u, n] = local[u] & ( broadcast_hit(u, n) | direct_hit(u, n) )
    broadcast_hit = kind[n]==BROADCAST and (user_mask[u] & frame_mask[n]) != 0
    direct_hit    = kind[n]==DIRECT    and dest[n] == u
    local         = owners[u] == my_index   (precomputed on entry)

This is the vectorized twin of ``get_interested_by_topic`` +
``get_broker_identifier_of_user`` dispatch (cdn-broker routing core,
tasks/broker/handler.rs:197-272), fused so the delivery matrix is produced
in one VMEM pass. Invalid slots must be pre-masked by the caller (kind=0).

Tiling: users ride the sublane axis (8/tile), frames the lane axis
(128/tile) — int32-native VPU shapes. Inputs are row/column vectors
broadcast into each tile, so HBM traffic is O(U + N), not O(U×N).

Off-TPU the kernel runs in interpreter mode; the pure-jnp reference
implementation is exported for equivalence tests and as the XLA-fusion
baseline the kernel must beat. The output is ``bool``: Pallas carries it
as int32 across the kernel boundary (Mosaic has no 1-bit memrefs) and
converts after the call, so the (8, 128) int32 tiling below holds for it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from pushcdn_tpu.proto.message import KIND_BROADCAST, KIND_DIRECT

TILE_U = 8     # sublane tile (int32 min sublane = 8)
TILE_N = 128   # lane tile


def delivery_matrix_reference(user_masks: jax.Array, local: jax.Array,
                              frame_tmask: jax.Array, kind: jax.Array,
                              dest: jax.Array) -> jax.Array:
    """Pure-jnp reference. ``local`` is bool[U] (owners == my_index);
    ``kind`` must already be 0 on invalid slots. Masks are either [U]/[N]
    (one u32 word, topics 0..31) or [U, W]/[N, W] (multi-word masks
    covering the reference's full u8 topic space at W=8)."""
    U = user_masks.shape[0]
    N = frame_tmask.shape[0]
    is_b = kind == KIND_BROADCAST
    is_d = kind == KIND_DIRECT
    if user_masks.ndim == 1:
        bcast = (user_masks[:, None] & frame_tmask[None, :]) != 0
    else:
        bcast = ((user_masks[:, None, :] & frame_tmask[None, :, :]) != 0
                 ).any(axis=-1)
    uidx = jax.lax.broadcasted_iota(jnp.int32, (U, N), 0)
    direct = dest[None, :] == uidx
    return ((bcast & is_b[None, :]) | (direct & is_d[None, :])) \
        & local[:, None]


def _make_kernel(W: int):
    def _kernel(umask_ref, local_ref, tmask_ref, kind_ref, dest_ref,
                out_ref):
        i = pl.program_id(0)
        umask = umask_ref[:]            # [TILE_U, W] uint32
        local = local_ref[:]            # [TILE_U, 1] int32 (0/1)
        tmask = tmask_ref[:]            # [W, TILE_N] uint32
        kind = kind_ref[:]              # [1, TILE_N] int32
        dest = dest_ref[:]              # [1, TILE_N] int32

        is_b = kind == KIND_BROADCAST
        is_d = kind == KIND_DIRECT
        # OR of the per-word AND — W is static, the loop unrolls
        bcast = (umask[:, 0:1] & tmask[0:1, :]) != 0    # [TILE_U, TILE_N]
        for w in range(1, W):
            bcast |= (umask[:, w:w + 1] & tmask[w:w + 1, :]) != 0
        # global user index of each tile row
        row = jax.lax.broadcasted_iota(jnp.int32, (TILE_U, TILE_N), 0) \
            + i * TILE_U
        direct = dest == row
        out_ref[:] = ((bcast & is_b) | (direct & is_d)) & (local != 0)
    return _kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def delivery_matrix_pallas(user_masks: jax.Array, local: jax.Array,
                           frame_tmask: jax.Array, kind: jax.Array,
                           dest: jax.Array,
                           interpret: bool = False) -> jax.Array:
    """Pallas version. Shapes: user_masks [U] or [U, W], local [U],
    frame_tmask [N] or [N, W], kind/dest [N]; U must be a multiple of
    TILE_U and N of TILE_N (the router pads)."""
    U = user_masks.shape[0]
    N = frame_tmask.shape[0]
    W = 1 if user_masks.ndim == 1 else user_masks.shape[1]
    grid = (U // TILE_U, N // TILE_N)
    return pl.pallas_call(
        _make_kernel(W),
        out_shape=jax.ShapeDtypeStruct((U, N), jnp.bool_),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_U, W), lambda i, j: (i, 0)),       # user_masks
            pl.BlockSpec((TILE_U, 1), lambda i, j: (i, 0)),       # local
            pl.BlockSpec((W, TILE_N), lambda i, j: (0, j)),       # tmask
            pl.BlockSpec((1, TILE_N), lambda i, j: (0, j)),       # kind
            pl.BlockSpec((1, TILE_N), lambda i, j: (0, j)),       # dest
        ],
        out_specs=pl.BlockSpec((TILE_U, TILE_N), lambda i, j: (i, j)),
        interpret=interpret,
    )(
        user_masks.reshape(U, W),
        local.astype(jnp.int32).reshape(U, 1),
        frame_tmask.reshape(N, W).T,
        kind.reshape(1, N),
        dest.reshape(1, N),
    )


def selects_pallas(U: int, N: int, use_pallas: bool | None = None) -> bool:
    """The dispatch rule of :func:`delivery_matrix`, readable from outside
    (the device plane logs it at start): Pallas on a real TPU — or when
    forced — and only where the shapes tile (``U`` by ``TILE_U``, ``N`` by
    ``TILE_N``). The served planes' wide lane (N=64) and latency slice
    (N=8) do not, so they take the XLA-fused jnp reference by this rule
    on every backend."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    return bool(use_pallas) and U % TILE_U == 0 and N % TILE_N == 0


def delivery_matrix(user_masks, local, frame_tmask, kind, dest,
                    use_pallas: bool | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """Dispatch by :func:`selects_pallas`: the Pallas kernel compiled by
    Mosaic on a real TPU, the jnp reference everywhere else (the Pallas
    CPU interpreter walks the grid tile-by-tile in Python — ~9x slower
    than the fused XLA reference on an 8-shard CPU mesh step — so auto
    mode only picks the kernel where it compiles; pass ``use_pallas=True``
    explicitly to test interpreter equivalence). On a TPU the kernel is
    never interpreted, and a kernel that fails to compile raises: there
    is no retry on the jnp twin."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if selects_pallas(user_masks.shape[0], frame_tmask.shape[0], use_pallas):
        return delivery_matrix_pallas(user_masks, local, frame_tmask,
                                      kind, dest, interpret=interpret)
    return delivery_matrix_reference(user_masks, local, frame_tmask,
                                     kind, dest)
