"""The device router: broadcast/direct fan-out over a broker-mesh axis.

This is the TPU lowering of the broker hot path (SURVEY.md §2e / §7 stage
7). The reference routes by hash-map lookups and per-peer TCP writes
(cdn-broker/src/tasks/broker/handler.rs:197-272); here one jitted step,
run under ``shard_map`` over the ``"brokers"`` mesh axis, does the same
work for a whole batch at once:

- **inter-broker hop** = one ``all_gather`` of the frame tensors over the
  broker axis (ICI) — every frame crosses the mesh exactly once, the
  vectorized analog of the reference's "deserialize once per hop, forward
  raw bytes" rule;
- **CRDT sync** rides the same step: per-shard DirectMap claims are
  all-gathered and folded with the versioned dominance rule
  (pushcdn_tpu.parallel.crdt) — the 10 s sync task becomes a per-step
  merge, and user topic masks travel with the ownership claim;
- **broadcast routing** = a topic-bitmask AND between every gathered frame
  and every local user (VPU; optionally the Pallas kernel in
  pushcdn_tpu.ops.topic_kernel);
- **direct routing** = equality match of the frame's destination user slot
  against locally-owned users — delivery-iff-owner makes the reference's
  ``to_user_only`` loop-prevention rule structural: nothing is ever
  re-forwarded;
- **double-connect eviction** falls out of the merge's changed-mask
  (``evictions``), exactly like ``apply_user_sync``'s kick list.

Outputs stay on device as ``(gathered frames, delivery mask)``; the host
egress pump walks the mask to enqueue frame bytes to user sockets.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from pushcdn_tpu.parallel.crdt import (
    ABSENT,
    CrdtState,
    empty_state,
    merge_all_gathered_with_payload,
)
from pushcdn_tpu.ops.delivery_kernel import delivery_matrix
from pushcdn_tpu.ops.ragged_delivery import ragged_delivery

BROKER_AXIS = "brokers"

# None = auto (Pallas on TPU / interpreter elsewhere when shapes align);
# flip to False to force the jnp reference path (bench comparisons).
# `bench.py --delivery-impl {auto,pallas,jnp,ragged}` sets this before the
# first routing_step trace — the one-command delivery-impl A/B.
USE_PALLAS_DELIVERY: Optional[bool] = None

# The selected delivery implementation by name (None = auto). "ragged"
# switches consumers (bench.py, DevicePlane) onto the paged walk
# (ops.ragged_delivery) with the dense kernel kept as the in-repo twin.
DELIVERY_IMPL: Optional[str] = None

# Pallas-vs-jnp switch for the RAGGED kernel specifically (None = auto:
# Pallas on real TPU, jnp twin elsewhere — same policy as the dense flag).
RAGGED_USE_PALLAS: Optional[bool] = None


def set_delivery_impl(impl: str) -> None:
    """One switch for every delivery-impl consumer: 'auto' restores the
    backend-keyed default, 'pallas'/'jnp' force the dense kernel's mode,
    'ragged' selects the paged walk (jnp twin off-TPU)."""
    global DELIVERY_IMPL, USE_PALLAS_DELIVERY
    if impl not in ("auto", "pallas", "jnp", "ragged"):
        raise ValueError(f"unknown delivery impl {impl!r}")
    DELIVERY_IMPL = None if impl == "auto" else impl
    USE_PALLAS_DELIVERY = {"pallas": True, "jnp": False}.get(impl)


# ---------------------------------------------------------------------------
# collective accounting (the one-collective-per-tick invariant)
# ---------------------------------------------------------------------------
#
# Every collective the router's programs issue goes through the two
# helpers below, which bump a trace-time counter — ``trace_collectives()``
# deltas around a jit trace count a program's collectives without parsing
# HLO. ``count_collectives`` is the lowering-level twin (counts collective
# ops in ``jit(...).lower(...).as_text()``): the mesh dryrun test asserts
# BOTH agree that a fused tick is exactly one collective.

_TRACE_COLLECTIVES = [0]


def trace_collectives() -> int:
    """Collectives traced so far in this process (diff around a trace)."""
    return _TRACE_COLLECTIVES[0]


def _all_gather_counted(x: jax.Array, axis_name: str) -> jax.Array:
    _TRACE_COLLECTIVES[0] += 1
    return jax.lax.all_gather(x, axis_name)


def _all_to_all_counted(x: jax.Array, axis_name: str) -> jax.Array:
    _TRACE_COLLECTIVES[0] += 1
    return jax.lax.all_to_all(x, axis_name, 0, 0)


def count_collectives(lowered_text: str) -> int:
    """Count collective ops in a lowered program text. Feed it
    ``jit(step).lower(*args).as_text()`` (StableHLO — one textual op per
    collective); compiled HLO can split one collective into start/done
    pairs and is not a supported input."""
    return sum(lowered_text.count(op) for op in (
        "stablehlo.all_gather", "stablehlo.all_to_all",
        "stablehlo.all_reduce", "stablehlo.collective_permute"))


class RouterState(NamedTuple):
    """Per-shard routing state: the DirectMap twin + per-user topic masks."""

    crdt: CrdtState          # owners/versions/identities, each int32/uint32[U]
    topic_masks: jax.Array   # uint32[U] or uint32[U, W] — authoritative at
                             # the owner (W words cover 32·W topics)


class IngressBatch(NamedTuple):
    """One step of packed ingress frames (see parallel.frames)."""

    frame_bytes: jax.Array  # uint8[S, F]
    kind: jax.Array         # int32[S]
    length: jax.Array       # int32[S]
    topic_mask: jax.Array   # uint32[S] or uint32[S, W]
    dest: jax.Array         # int32[S]
    valid: jax.Array        # bool[S]


class DirectIngress(NamedTuple):
    """Per-destination-shard direct frames (see frames.DirectBuckets):
    axis 0 = destination shard. Exchanged with ONE ``all_to_all`` over the
    broker axis — each direct frame crosses ICI exactly once, to its owner
    (SURVEY.md §2e: the point-to-point collective keyed by owner-device
    index), instead of being all-gathered to every shard."""

    frame_bytes: jax.Array  # uint8[B, C, F]
    length: jax.Array       # int32[B, C]
    dest: jax.Array         # int32[B, C]
    valid: jax.Array        # bool[B, C]


class RouteResult(NamedTuple):
    gathered_bytes: jax.Array   # uint8[B*S, F] — every frame, post-ICI
    gathered_length: jax.Array  # int32[B*S]
    deliver: jax.Array          # bool[U, B*S] — local delivery matrix
    state: RouterState          # merged CRDT + masks
    evictions: jax.Array        # bool[U] — locally-owned users now owned elsewhere
    # all_to_all direct path (None when no DirectIngress was passed):
    direct_bytes: Optional[jax.Array] = None    # uint8[B*C, F] — received frames
    direct_length: Optional[jax.Array] = None   # int32[B*C]
    direct_deliver: Optional[jax.Array] = None  # bool[U, B*C]


def empty_router_state(num_users: int, topic_words: int = 1) -> RouterState:
    shape = (num_users,) if topic_words == 1 else (num_users, topic_words)
    return RouterState(
        crdt=empty_state(num_users),
        topic_masks=jnp.zeros(shape, dtype=jnp.uint32),
    )


def _merge_gathered(state: RouterState, g_owners, g_versions, g_ids,
                    g_masks, my_index, liveness):
    """The shared CRDT anti-entropy fold over already-gathered state rows
    (one copy of the merge/liveness/eviction logic for the per-array,
    fused-packed, and ragged steps)."""
    was_local = state.crdt.owners == my_index
    merged, masks, _changed = merge_all_gathered_with_payload(
        state.crdt, state.topic_masks,
        CrdtState(g_owners, g_versions, g_ids), g_masks)
    if liveness is not None:
        # release every slot owned by a dead shard (owner index is a mesh
        # coordinate; ABSENT maps to "live" so tombstones pass through)
        owner_live = jnp.where(merged.owners == ABSENT, True,
                               liveness[jnp.clip(merged.owners, 0)])
        merged = CrdtState(
            owners=jnp.where(owner_live, merged.owners, ABSENT),
            versions=jnp.where(owner_live, merged.versions,
                               merged.versions + 1),
            identities=merged.identities,
        )
        live_b = owner_live.reshape(
            owner_live.shape + (1,) * (masks.ndim - owner_live.ndim))
        masks = jnp.where(live_b, masks, 0)
    now_local = merged.owners == my_index
    evictions = was_local & ~now_local
    return merged, masks, now_local, evictions


def _lane_deliver(masks, now_local, g_bytes, g_kind, g_length, g_tmask,
                  g_dest, g_valid, liveness) -> LaneDelivery:
    """One broadcast lane's delivery matrix from gathered frame columns."""
    B, S = g_kind.shape
    if liveness is not None:
        g_valid = g_valid & liveness[:, None]  # dead shards' frames
    valid_f = g_valid.reshape(B * S)
    kind_f = jnp.where(valid_f, g_kind.reshape(B * S), 0)
    # topic masks may be multi-word ([.., W]) for >32-topic spaces
    tmask_f = g_tmask.reshape((B * S,) + g_tmask.shape[2:])
    deliver = delivery_matrix(
        masks, now_local, tmask_f, kind_f,
        g_dest.reshape(B * S), use_pallas=USE_PALLAS_DELIVERY)
    return LaneDelivery(
        gathered_bytes=(None if g_bytes is None
                        else g_bytes.reshape(B * S, -1)),
        gathered_length=g_length.reshape(B * S),
        deliver=deliver)


def _direct_deliver(r_bytes, r_length, r_dest, r_valid, now_local,
                    liveness) -> LaneDelivery:
    """Build the local delivery mask from RECEIVED direct buckets (axis 0
    = source shard post-exchange). Delivery is iff the addressed slot is
    locally owned — ownership moves race exactly like the reference's
    forward-to-old-owner during CRDT convergence, and resolve the same
    way (deliver-iff-owner, never re-forward)."""
    if liveness is not None:
        # a dead shard's stale frames (in flight when it was declared
        # down) never deliver
        r_valid = r_valid & liveness[:, None]
    B, C = r_dest.shape
    dest_f = r_dest.reshape(B * C)
    valid_f = r_valid.reshape(B * C)
    U = now_local.shape[0]
    slots = jnp.arange(U, dtype=jnp.int32)
    deliver = (valid_f[None, :]
               & (dest_f[None, :] == slots[:, None])
               & now_local[:, None])
    return LaneDelivery(
        gathered_bytes=(None if r_bytes is None
                        else r_bytes.reshape(B * C, -1)),
        gathered_length=r_length.reshape(B * C),
        deliver=deliver)


def _direct_route(direct: DirectIngress, now_local: jax.Array,
                  axis_name: Optional[str],
                  liveness: Optional[jax.Array] = None,
                  gather_bytes: bool = True):
    """Exchange per-destination buckets and build the local delivery mask.

    ``all_to_all`` swaps the destination-shard axis for a source-shard
    axis: received[j] = what shard j staged for *this* shard."""
    if axis_name is None:
        r_bytes, r_length, r_dest, r_valid = (
            direct.frame_bytes, direct.length, direct.dest, direct.valid)
    else:
        r_bytes = (_all_to_all_counted(direct.frame_bytes, axis_name)
                   if gather_bytes else None)
        r_length = _all_to_all_counted(direct.length, axis_name)
        r_dest = _all_to_all_counted(direct.dest, axis_name)
        r_valid = _all_to_all_counted(direct.valid, axis_name)
    lane = _direct_deliver(r_bytes, r_length, r_dest, r_valid, now_local,
                           liveness)
    return lane.gathered_bytes, lane.gathered_length, lane.deliver


def routing_step(state: RouterState, batch: IngressBatch,
                 my_index: jax.Array, axis_name: Optional[str],
                 direct: Optional[DirectIngress] = None
                 ) -> RouteResult:
    """One routing step for one broker shard — the single-lane special case
    of :func:`routing_step_lanes` (one copy of the collective/merge logic).

    With ``axis_name=None`` this is the single-broker fast path (no
    collectives — the degenerate mesh). Under ``shard_map`` the gathers run
    over ICI.
    """
    r = routing_step_lanes(state, (batch,), my_index, axis_name,
                           directs=() if direct is None else (direct,))
    lane = r.lanes[0]
    d = r.direct_lanes[0] if r.direct_lanes else None
    return RouteResult(
        gathered_bytes=lane.gathered_bytes,
        gathered_length=lane.gathered_length,
        deliver=lane.deliver,
        state=r.state,
        evictions=r.evictions,
        direct_bytes=None if d is None else d.gathered_bytes,
        direct_length=None if d is None else d.gathered_length,
        direct_deliver=None if d is None else d.deliver,
    )


# ---------------------------------------------------------------------------
# size-bucketed lanes (SURVEY.md §7 hard-part #1)
# ---------------------------------------------------------------------------
#
# One fixed frame size can't serve 100 B acks and 32 KB proposals at once:
# sizing slots for the big ones wastes HBM and ICI bandwidth on padding,
# sizing for the small ones bounces everything else to the host path. A
# *lane* is an independently-shaped FrameRing (slots × frame_bytes); the
# lane step routes any number of lanes in ONE jitted program with ONE CRDT
# merge — per-lane all_gathers over the broker axis, per-lane delivery
# matrices against the same merged ownership/mask state.


class LaneDelivery(NamedTuple):
    """Per-lane router output: the gathered frames + delivery matrix."""

    gathered_bytes: jax.Array   # uint8[B*S_l, F_l]
    gathered_length: jax.Array  # int32[B*S_l]
    deliver: jax.Array          # bool[U, B*S_l]


class MultiRouteResult(NamedTuple):
    lanes: tuple                # Tuple[LaneDelivery, ...] (broadcast lanes)
    direct_lanes: tuple         # Tuple[LaneDelivery, ...] (all_to_all lanes)
    state: RouterState
    evictions: jax.Array        # bool[U]


def routing_step_lanes(state: RouterState,
                       batches: tuple,
                       my_index: jax.Array,
                       axis_name: Optional[str],
                       directs: tuple = (),
                       liveness: Optional[jax.Array] = None,
                       gather_bytes: bool = True,
                       fused: bool = False,
                       ) -> MultiRouteResult:
    """One routing step over any number of size-bucketed lanes.

    ``batches`` is a tuple of :class:`IngressBatch` (one per broadcast
    lane, any slot counts / frame widths); ``directs`` a tuple of
    :class:`DirectIngress` (one per direct lane). The CRDT/topic-mask
    merge runs ONCE; every lane's delivery matrix is computed against the
    same merged state, so cross-lane semantics are identical to a single
    ring — a lane is purely a shape bucket.

    ``gather_bytes=False`` skips the frame-byte collectives entirely
    (lanes come back with ``gathered_bytes=None``): on a single-host
    multi-chip topology every shard's staged frames already live in the
    one host's memory, so moving payload bytes over ICI and back through
    D2H is pure waste — only the *delivery decision* needs the mesh. The
    egress pump reads payloads from the host ring snapshots instead
    (broker/mesh_group.py). Multi-host deployments keep the default: a
    remote host's frame bytes exist nowhere locally except via the
    step's collectives.

    ``liveness`` (bool[B], identical on every shard) is the dynamic-
    membership mask over the STATIC device mesh (SURVEY.md §7 hard-part
    #3): the physical mesh can't churn the way the reference's broker
    mesh does (heartbeat.rs:69-107), so a departed shard is instead
    declared dead by the host control plane. In-step that means (a) its
    gathered frames never deliver, and (b) every slot it owned is
    tombstoned with a deterministic version bump — all shards compute the
    identical release from the identical gathered state, so the CRDT
    stays convergent, exactly like the reference aging a dead broker's
    users out of the DirectMap.

    ``fused=True`` re-expresses the whole inter-broker hop as ONE
    sharding-aware collective (see :func:`_routing_step_lanes_fused`).
    """
    if fused and axis_name is not None:
        return _routing_step_lanes_fused(state, batches, my_index,
                                         axis_name, directs, liveness,
                                         gather_bytes)

    def gather(x):
        if axis_name is None:
            return x[None]
        return _all_gather_counted(x, axis_name)

    # ---- CRDT anti-entropy: once, shared by every lane -------------------
    merged, masks, now_local, evictions = _merge_gathered(
        state, gather(state.crdt.owners), gather(state.crdt.versions),
        gather(state.crdt.identities), gather(state.topic_masks),
        my_index, liveness)

    # ---- per-lane inter-broker hop + delivery matrix ---------------------
    lanes = []
    for batch in batches:
        lanes.append(_lane_deliver(
            masks, now_local,
            gather(batch.frame_bytes) if gather_bytes else None,
            gather(batch.kind), gather(batch.length),
            gather(batch.topic_mask), gather(batch.dest),
            gather(batch.valid), liveness))

    direct_lanes = []
    for direct in directs:
        d_bytes, d_length, d_deliver = _direct_route(
            direct, now_local, axis_name, liveness,
            gather_bytes=gather_bytes)
        direct_lanes.append(LaneDelivery(
            gathered_bytes=d_bytes, gathered_length=d_length,
            deliver=d_deliver))

    return MultiRouteResult(
        lanes=tuple(lanes), direct_lanes=tuple(direct_lanes),
        state=RouterState(crdt=merged, topic_masks=masks),
        evictions=evictions)


# ---------------------------------------------------------------------------
# the fused one-collective tick
# ---------------------------------------------------------------------------
#
# The per-array step above issues 4 state gathers + 5-6 gathers per lane +
# 3-4 all_to_alls per direct lane — a dozen-plus collectives per tick,
# each paying its own dispatch latency. Following the array-redistribution
# decomposition of "Memory-efficient array redistribution through portable
# collective communication" (PAPERS.md), the whole tick's exchange is ONE
# redistribution over a packed ragged buffer: every gathered leaf is
# bitcast to u32 words and concatenated (the per-shard segment layout is a
# trace-time constant), one all_gather moves it, and the leaves are sliced
# back out of the [B, L] result. The per-lane all_to_all of the direct
# path folds into the same collective: an all_to_all is an all_gather
# composed with a local slice (each shard keeps column ``my_index`` of the
# gathered destination axis), so directs ride the one buffer too — at a
# B-fold redundancy on direct payload bytes, which the single-host planes
# (gather_bytes=False, metadata only) never pay; multi-host deployments
# that gather payload can flip ``fused=False`` to get the leaner
# two-schedule form back.


class _WordPacker:
    """Trace-time leaf packer: add() bitcasts each array to u32 words,
    pack() concatenates, unpack() slices a gathered [B, L] buffer back
    into [B, ...]-shaped leaves in add() order."""

    def __init__(self):
        self._parts = []
        self._specs = []  # (kind, shape, pad)

    def add(self, x: jax.Array) -> None:
        shape = x.shape
        if x.dtype == jnp.bool_:
            words = x.astype(jnp.uint32).reshape(-1)
            self._specs.append(("bool", shape, 0))
        elif x.dtype == jnp.uint8:
            flat = x.reshape(-1)
            pad = (-flat.shape[0]) % 4
            if pad:
                flat = jnp.concatenate([flat, jnp.zeros(pad, jnp.uint8)])
            words = jax.lax.bitcast_convert_type(
                flat.reshape(-1, 4), jnp.uint32)
            self._specs.append(("u8", shape, pad))
        elif x.dtype == jnp.uint32:
            words = x.reshape(-1)
            self._specs.append(("u32", shape, 0))
        elif x.dtype == jnp.int32:
            words = jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
            self._specs.append(("i32", shape, 0))
        else:  # pragma: no cover - router leaves are the four above
            raise TypeError(f"unpackable dtype {x.dtype}")
        self._parts.append(words)

    def pack(self) -> jax.Array:
        return jnp.concatenate(self._parts)

    def unpack(self, gathered: jax.Array) -> list:
        B = gathered.shape[0]
        outs = []
        off = 0
        for (kind, shape, pad), part in zip(self._specs, self._parts):
            n = part.shape[0]
            words = gathered[:, off:off + n]
            off += n
            if kind == "bool":
                out = (words != 0).reshape((B,) + shape)
            elif kind == "u8":
                u8 = jax.lax.bitcast_convert_type(
                    words, jnp.uint8).reshape(B, -1)
                if pad:
                    u8 = u8[:, :-pad]
                out = u8.reshape((B,) + shape)
            elif kind == "u32":
                out = words.reshape((B,) + shape)
            else:
                out = jax.lax.bitcast_convert_type(
                    words, jnp.int32).reshape((B,) + shape)
            outs.append(out)
        return outs


def _routing_step_lanes_fused(state: RouterState, batches: tuple,
                              my_index: jax.Array, axis_name: str,
                              directs: tuple,
                              liveness: Optional[jax.Array],
                              gather_bytes: bool) -> MultiRouteResult:
    """One-collective tick: pack → all_gather → unpack → the same merge
    and delivery math as the per-array step (bit-identical outputs)."""
    pk = _WordPacker()
    pk.add(state.crdt.owners)
    pk.add(state.crdt.versions)
    pk.add(state.crdt.identities)
    pk.add(state.topic_masks)
    for batch in batches:
        if gather_bytes:
            pk.add(batch.frame_bytes)
        pk.add(batch.kind)
        pk.add(batch.length)
        pk.add(batch.topic_mask)
        pk.add(batch.dest)
        pk.add(batch.valid)
    for direct in directs:
        if gather_bytes:
            pk.add(direct.frame_bytes)
        pk.add(direct.length)
        pk.add(direct.dest)
        pk.add(direct.valid)

    # the tick's ONE collective
    gathered = _all_gather_counted(pk.pack(), axis_name)
    fields = iter(pk.unpack(gathered))

    merged, masks, now_local, evictions = _merge_gathered(
        state, next(fields), next(fields), next(fields), next(fields),
        my_index, liveness)

    lanes = []
    for _batch in batches:
        g_bytes = next(fields) if gather_bytes else None
        lanes.append(_lane_deliver(
            masks, now_local, g_bytes, next(fields), next(fields),
            next(fields), next(fields), next(fields), liveness))

    def sel(x):
        # the all_to_all re-expressed post-gather: keep column `my_index`
        # of the gathered destination axis (received[src] = what src
        # staged for THIS shard)
        if x is None:
            return None
        return jax.lax.dynamic_index_in_dim(x, my_index, axis=1,
                                            keepdims=False)

    direct_lanes = []
    for _direct in directs:
        g_bytes = next(fields) if gather_bytes else None
        g_length = next(fields)
        g_dest = next(fields)
        g_valid = next(fields)
        direct_lanes.append(_direct_deliver(
            sel(g_bytes), sel(g_length), sel(g_dest), sel(g_valid),
            now_local, liveness))

    return MultiRouteResult(
        lanes=tuple(lanes), direct_lanes=tuple(direct_lanes),
        state=RouterState(crdt=merged, topic_masks=masks),
        evictions=evictions)


# ---------------------------------------------------------------------------
# the ragged delivery step (single-shard planes + bench)
# ---------------------------------------------------------------------------


class RaggedRouteResult(NamedTuple):
    """Compact per-candidate delivery output: row ``w`` of ``out_user``
    is a receiver run for frame ``walk_frame[w]`` (-1 lanes empty)."""

    out_user: jax.Array  # int32[Wp, PAGE]
    counts: jax.Array    # int32[Wp]
    state: RouterState
    evictions: jax.Array


def routing_step_ragged(state: RouterState, batch: IngressBatch,
                        pages: jax.Array, walk_page: jax.Array,
                        walk_frame: jax.Array, my_index: jax.Array,
                        use_pallas: Optional[bool] = None,
                        interpret: Optional[bool] = None
                        ) -> RaggedRouteResult:
    """One single-shard routing step through the ragged paged kernel
    (ops.ragged_delivery): the same CRDT fold as the dense step, then a
    page walk instead of the U x N sweep. The walk inputs come from
    ``RaggedInterest.pack`` on the host. Single-shard by design — the
    mesh planes keep the dense kernel (their fan-out is dominated by the
    gathered frame set); the ragged walk is where the single-broker
    fan-out cost lives."""
    merged, masks, now_local, evictions = _merge_gathered(
        state, state.crdt.owners[None], state.crdt.versions[None],
        state.crdt.identities[None], state.topic_masks[None],
        my_index, None)
    kind_f = jnp.where(batch.valid, batch.kind, 0)
    if use_pallas is None:
        use_pallas = RAGGED_USE_PALLAS
    out_user, counts = ragged_delivery(
        pages, walk_page, walk_frame, now_local, masks,
        batch.topic_mask, kind_f, batch.dest,
        use_pallas=use_pallas, interpret=interpret)
    return RaggedRouteResult(
        out_user=out_user, counts=counts,
        state=RouterState(crdt=merged, topic_masks=masks),
        evictions=evictions)


# ---------------------------------------------------------------------------
# jitted entry points
# ---------------------------------------------------------------------------

@jax.jit
def routing_step_single(state: RouterState, batch: IngressBatch
                        ) -> RouteResult:
    """Single-chip step (mesh of one): the compile-checked `entry()` path."""
    return routing_step(state, batch, jnp.int32(0), axis_name=None)


import functools


@functools.partial(jax.jit, static_argnames=("gather_bytes",))
def routing_step_lanes_single(state: RouterState, batches: tuple,
                              directs: tuple = (),
                              gather_bytes: bool = True
                              ) -> MultiRouteResult:
    """Single-chip lane step (a change in the number of lanes is a pytree
    structure change, so jit retraces per lane-set shape).
    ``gather_bytes=False`` keeps frame bytes out of the step entirely —
    the single-shard plane's egress reads them from the host ring
    snapshot, so only the delivery matrix crosses PCIe back."""
    return routing_step_lanes(state, batches, jnp.int32(0), axis_name=None,
                              directs=directs, gather_bytes=gather_bytes)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def routing_step_ragged_single(state: RouterState, batch: IngressBatch,
                               pages: jax.Array, walk_page: jax.Array,
                               walk_frame: jax.Array,
                               use_pallas: Optional[bool] = None,
                               interpret: Optional[bool] = None
                               ) -> RaggedRouteResult:
    """Jitted single-chip ragged step (walk shapes key the jit cache —
    ``RaggedInterest.pack`` pads them to WALK_ROUND granules)."""
    return routing_step_ragged(state, batch, pages, walk_page, walk_frame,
                               jnp.int32(0), use_pallas=use_pallas,
                               interpret=interpret)


class LaneWords:
    """Where every lane's step inputs of one tick sit in ONE u32 row a
    shard, so that a tick's host->device traffic is one transfer: a
    broadcast lane's ``kind``, ``length``, ``topic_mask``, ``dest`` and
    ``valid``, then a direct lane's ``length``, ``dest`` and ``valid``,
    each in :class:`_WordPacker`'s word form (an i32 bitcast, a bool one
    word) and in the order the fused tick packs them for its collective.
    The host fills a ``[B, width]`` buffer (:meth:`pack`, slice
    assignment into rows) and the step slices a shard's row back into
    lanes at the same static offsets (:meth:`unpack`). The geometry is
    the lanes' own: ``ring_slots`` one S a broadcast lane,
    ``bucket_shapes`` one (B, C) a direct lane, ``mask_words`` the
    trailing shape of a topic mask (``()`` or ``(W,)``)."""

    _BATCH = (("kind", "i32"), ("length", "i32"), ("topic_mask", "u32"),
              ("dest", "i32"), ("valid", "bool"))
    _DIRECT = (("length", "i32"), ("dest", "i32"), ("valid", "bool"))

    def __init__(self, ring_slots, bucket_shapes, mask_words=()):
        from math import prod
        self.width = 0

        def place(fields, shape):
            out = []
            for name, form in fields:
                full = tuple(shape) + (tuple(mask_words)
                                       if name == "topic_mask" else ())
                out.append((name, form, self.width, prod(full), full))
                self.width += prod(full)
            return out

        # [lane][field] -> (name, word form, offset, words, leaf shape)
        self.lanes = [place(self._BATCH, (s,)) for s in ring_slots]
        self.direct_lanes = [place(self._DIRECT, bc) for bc in bucket_shapes]

    def pack(self, buf, batches, directs) -> None:
        """Write [lane][shard] host snapshots (``frames.FrameBatch``,
        ``frames.DirectBatch``) into ``buf``, ``uint32[B, width]``: every
        word of it, so a buffer can be reused from tick to tick."""
        for fields, lane in zip(self.lanes + self.direct_lanes,
                                list(batches) + list(directs)):
            for name, form, off, n, _shape in fields:
                for row, snap in zip(buf, lane):
                    words = row[off:off + n]
                    if form == "i32":
                        words = words.view("int32")
                    words[:] = getattr(snap, name).reshape(-1)

    def unpack(self, words: jax.Array, lane_bytes: tuple,
               direct_bytes: tuple):
        """One shard's row back into ``(batches, directs)``, each lane
        with the ``frame_bytes`` leaf it is given."""
        if words.shape != (self.width,):
            raise ValueError(f"a shard's row of {self.width} words, got "
                             f"{words.shape}")

        def leaves(fields):
            for _name, form, off, n, shape in fields:
                leaf = words[off:off + n].reshape(shape)
                if form == "bool":
                    leaf = leaf != 0
                elif form == "i32":
                    leaf = jax.lax.bitcast_convert_type(leaf, jnp.int32)
                yield leaf

        return (tuple(IngressBatch(b, *leaves(f))
                      for b, f in zip(lane_bytes, self.lanes)),
                tuple(DirectIngress(d, *leaves(f))
                      for d, f in zip(direct_bytes, self.direct_lanes)))


def make_mesh_lane_step(mesh: Mesh, gather_bytes: bool = True,
                        fused: bool = False):
    """Build the multi-chip lane step: every leaf of (state, batches,
    directs) is stacked on a leading broker axis and sharded over the mesh;
    one jitted shard_map program routes all lanes (one shared CRDT merge).
    ``liveness`` is stacked [B, B] (every shard carries the full
    membership mask). ``gather_bytes=False`` builds the single-host
    variant whose lanes skip the frame-byte collectives (see
    :func:`routing_step_lanes`). ``fused=True`` builds the
    one-collective-per-tick variant: the whole exchange rides a single
    packed all_gather (see :func:`_routing_step_lanes_fused`).

    The step has two entries. Per array: ``batches`` and ``directs`` are
    tuples of stacked :class:`IngressBatch` / :class:`DirectIngress`.
    Packed, with ``words``: every lane's metadata arrives as one
    ``uint32[B, W]`` buffer in :class:`LaneWords`' layout, and ``batches``
    and ``directs`` hold each lane's stacked ``frame_bytes`` leaf alone
    (``[B, S, F]``, ``[B, B, C, F]``; F = 0 where nothing gathers
    bytes), which also gives the lanes' geometry; the row is sliced back
    into lanes before the same routing math."""

    def per_shard(state: RouterState, batches: tuple, directs: tuple,
                  liveness: jax.Array, words: Optional[jax.Array]):
        state = jax.tree.map(lambda x: x[0], state)
        batches = jax.tree.map(lambda x: x[0], batches)
        directs = jax.tree.map(lambda x: x[0], directs)
        if words is not None:
            batches, directs = LaneWords(
                [b.shape[0] for b in batches],
                [d.shape[:2] for d in directs],
                state.topic_masks.shape[1:]).unpack(
                    words[0], batches, directs)
        my = jax.lax.axis_index(BROKER_AXIS).astype(jnp.int32)
        result = routing_step_lanes(state, batches, my,
                                    axis_name=BROKER_AXIS, directs=directs,
                                    liveness=liveness[0],
                                    gather_bytes=gather_bytes,
                                    fused=fused)
        return jax.tree.map(lambda x: x[None], result)

    sharded = jax.shard_map(
        per_shard, mesh=mesh, in_specs=(P(BROKER_AXIS),) * 5,
        out_specs=P(BROKER_AXIS), check_vma=False)

    @jax.jit
    def step(state, batches, directs, liveness=None, words=None):
        if liveness is None:
            B = mesh.devices.size
            liveness = jnp.ones((B, B), dtype=bool)
        return sharded(state, batches, directs, liveness, words)

    return step


def make_mesh_routing_step(mesh: Mesh, with_direct: bool = False):
    """Build the multi-chip step: state+batch sharded over the broker axis,
    one jitted shard_map program (SURVEY.md §7 stage 7: broker shards ↔
    devices of a jax mesh). With ``with_direct`` the step also takes
    stacked :class:`DirectIngress` buckets ([B_src, B_dest, C, F]) and runs
    the one-hop ``all_to_all`` direct path inside the same program."""

    def per_shard(state_leaves, batch_leaves, *direct_leaves):
        state = RouterState(CrdtState(*state_leaves[:3]), state_leaves[3])
        batch = IngressBatch(*batch_leaves)
        # shard_map gives each shard its [1, ...] block; drop the outer axis
        state = jax.tree.map(lambda x: x[0], state)
        batch = jax.tree.map(lambda x: x[0], batch)
        direct = None
        if direct_leaves:
            direct = DirectIngress(*(x[0] for x in direct_leaves[0]))
        my = jax.lax.axis_index(BROKER_AXIS).astype(jnp.int32)
        result = routing_step(state, batch, my, axis_name=BROKER_AXIS,
                              direct=direct)
        # re-add the sharded leading axis for the outputs
        return jax.tree.map(lambda x: x[None], tuple(result))

    n_in = 3 if with_direct else 2
    sharded = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=tuple(P(BROKER_AXIS) for _ in range(n_in)),
        out_specs=P(BROKER_AXIS), check_vma=False)

    def _unpack(out):
        return RouteResult(
            gathered_bytes=out[0], gathered_length=out[1], deliver=out[2],
            state=out[3], evictions=out[4],
            direct_bytes=out[5], direct_length=out[6], direct_deliver=out[7])

    if with_direct:
        @jax.jit
        def step(state_stacked: RouterState, batch_stacked: IngressBatch,
                 direct_stacked: DirectIngress):
            out = sharded(
                tuple((*state_stacked.crdt, state_stacked.topic_masks)),
                tuple(batch_stacked), tuple(direct_stacked))
            return _unpack(out)
    else:
        @jax.jit
        def step(state_stacked: RouterState, batch_stacked: IngressBatch):
            out = sharded(
                tuple((*state_stacked.crdt, state_stacked.topic_masks)),
                tuple(batch_stacked))
            return _unpack(out)

    return step
