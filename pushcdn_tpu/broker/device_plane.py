"""DevicePlane — the bridge that puts the TPU router in the broker's hot
path.

The host broker (tasks/handlers.py) routes per-message with dict lookups;
with a ``DevicePlane`` attached, eligible messages (wire frames that fit a
frame slot) are instead **staged into the frame ring, routed in batched
jitted steps on the attached device, and delivered from the resulting
delivery matrix** (SURVEY.md §7 stage 7 → stage 8 "edge": the socket⇄HBM
pump). The wire frame travels verbatim through HBM, so receivers are
byte-identical with the host path. Oversized messages and control traffic
keep the host path.

Scope (round 1): one broker = one device shard (``routing_step_lanes_single``).
The host CRDT stays authoritative for cross-broker ownership; the device
plane handles the local fan-out — which is where the per-message Python
cost lives. Multi-shard meshes route via parallel.router's shard_map step.

Consistency design (single-writer, snapshot-per-step):

- The **host mirrors** (``_owned`` bool[U], ``_masks`` u32[U, 8]) are the
  source of truth, mutated only on the event loop by the Connections
  observer hooks. Each step SNAPSHOTS them together with ``take_batch()``
  (same event-loop tick), and the device ``RouterState`` is rebuilt from
  that snapshot — a registration or subscription racing the in-flight step
  simply lands in the next snapshot, never lost.
- **Slot quarantine**: a released user slot is not reusable until the step
  that might still carry frames addressed to it has completed — prevents a
  recycled slot from leaking one user's messages to another.
- **The table follows who connects**: when a connection finds the slot
  table full, table and mirrors double on the event loop, in the observer
  hook (``MAX_USER_SLOTS`` is the ceiling; users beyond it are host-routed
  and keep every broadcast on the host path). A step in flight keeps the
  copies it took; no binding moves and no quarantined slot is freed, so
  both guarantees above hold across a growth. A step runs at the
  smallest power of two that holds the slot high-water mark
  (``effective_users``: a 1,000-user broker runs the 1,024-row programs,
  one of 5,000 the 8,192-row ones: ``_step_users``), and only the pump
  runs steps: a growth wakes it to load the new size's programs beside
  the connects that caused it (``_load_programs``).
- **A failed warm-up is fatal**: if the first compile-and-run raises
  (no usable device, a kernel Mosaic refuses), ``start`` raises and the
  broker exits non-zero — a broker asked for a device plane never serves
  as a silent host broker.
- **A mid-run failure protects acknowledged frames**: if a later step
  raises, its staged frames are re-routed on the host path (users-only,
  matching what the device would have delivered) and the plane disables
  itself; staging then always returns INELIGIBLE. The state is exported
  as ``cdn_device_plane_disabled`` and in ``/debug/topology``.

Flow per step:
  ingress: user_receive_loop → try_stage() → FrameRing (slot credits)
  compute: snapshot + take_batch → routing_step_lanes_single (jitted)
  egress:  deliver[u, f] → per-user non-blocking send of the frame bytes
  drain:   after an egress the pump wrote itself (the loop stood still),
           the receive loops stage what the sockets hold before the take
  pace:    before that, after a step that sent in the native batch though
           its take found room, the take waits until the wall since the
           last take has caught up with the CPU spent since it
"""

from __future__ import annotations

import asyncio
import logging
import time
from dataclasses import dataclass
from typing import TYPE_CHECKING, List, Optional, Tuple

import numpy as np

from pushcdn_tpu.broker.pump_common import (
    CoalesceGate,
    CpuPacer,
    PumpAccount,
    RevCache,
    TopicMaskCache,
    effective_users,
)
from pushcdn_tpu.broker.staging import StageResult
from pushcdn_tpu.broker.tasks.senders import egress_delivery_rows
from pushcdn_tpu.parallel import spans
from pushcdn_tpu.parallel.crdt import ABSENT, CrdtState
from pushcdn_tpu.parallel.frames import (
    TOPIC_WORDS_FULL,
    FrameRing,
    UserSlots,
    mask_mirror_shape,
    mask_row_of,
    stage_best_fit,
)
from pushcdn_tpu.parallel.router import (
    IngressBatch,
    RouterState,
    routing_step_lanes_single,
)
from pushcdn_tpu.proto import flowclass
from pushcdn_tpu.proto.error import Error
from pushcdn_tpu.proto.limiter import Bytes
from pushcdn_tpu.proto.message import (
    KIND_BROADCAST,
    KIND_DIRECT,
    Broadcast,
    Direct,
)

if TYPE_CHECKING:
    from pushcdn_tpu.broker.broker import Broker

logger = logging.getLogger("pushcdn.broker.device")

# The user table follows who connects: it starts at
# ``DevicePlaneConfig.num_user_slots`` and doubles when a connection finds
# it full, up to this many slots; users beyond it are host-routed
# (``_unmirrored``). No representation of a slot id stops here — ids are
# int32 in the ``dest`` column, the ragged pages and the native egress,
# and the ragged extractor's u16 radix pass (``ragged_pairs``) is a fast
# path that falls back to a comparison sort. What bounds the table is
# what a step brings back: the dense decision is bool[users, ring_slots]
# per busy lane, 64 MiB through D2H and the egress scan at 65,536 x 1,024.
MAX_USER_SLOTS = 65536

# Loop passes the pump yields to let the receive loops stage what the
# sockets hold before it takes its batch (``DevicePlane._drain``). A frame
# in a socket is three passes from the rings: the selector's reader
# callback feeds the stream, the connection's reader task parses it, the
# user's receive loop stages it. The pump's own handle runs first in every
# pass, so it sees that frame in its fourth. No more than the chain: what
# arrives while the pump drains has no claim on this step, and waiting for
# it is coalescing, ``CoalesceGate``'s decision.
_DRAIN_PASSES = 4


@dataclass
class DevicePlaneConfig:
    # the user table's size at start; it grows with the connections
    # (MAX_USER_SLOTS)
    num_user_slots: int = 1024
    ring_slots: int = 1024
    frame_bytes: int = 2048
    # Size-bucketed lanes beyond the base (ring_slots × frame_bytes) ring
    # (SURVEY.md §7 hard-part #1): each entry is (frame_bytes, ring_slots).
    # A frame is staged into the smallest lane it fits, so 100 B acks don't
    # ride 32 KB-padded slots and 16 KB proposals still stay on device.
    extra_lanes: tuple = ((16384, 64),)
    # u32 words per topic mask: 8 covers the reference's whole u8 topic
    # space; 1 keeps compact masks (and the native batch packer) for
    # deployments with ≤32 topics
    topic_words: int = TOPIC_WORDS_FULL
    # Adaptive coalescing: a step fires immediately on a burst after idle
    # (latency regime) or when >= coalesce_min_frames are staged; a steady
    # trickle below the threshold waits batch_window_s to amortize step
    # dispatch.
    batch_window_s: float = 0.001
    coalesce_min_frames: int = 16
    # prefix-slice shapes for sparse traffic (one extra cached jit
    # specialization; collectives/D2H shrink ~ring/latency_slots x)
    latency_slots: int = 8
    # Depth-1 bypass: when the plane is COMPLETELY idle (no step in
    # flight, rings empty) and at most this many messages arrive in one
    # batch, route them on the host path immediately — the device's step
    # dispatch is a latency floor the sparse regime should never pay,
    # and the single-shard plane's host path covers exactly the same
    # local users. 0 disables (tests of staging mechanics do).
    bypass_max_items: int = 2
    # Delivery implementation: "auto" follows router.DELIVERY_IMPL (the
    # bench.py --delivery-impl switch, PUSHCDN_DELIVERY_IMPL env);
    # "ragged" forces the paged walk (ops.ragged_delivery — per-tick work
    # scales with fan-out, compact pairs feed egress_delivery_rows with
    # no bool[U,N] re-scan); "dense" forces the delivery-matrix kernels.
    delivery_impl: str = "auto"
    # page-pool capacity for the ragged interest index (PAGE-slot pages;
    # exhaustion falls the plane back to the dense step, never drops)
    ragged_max_pages: int = 1024
    # Relaxed-order pair extraction (ragged_pairs_grouped): a multi-topic
    # subscriber's same-tick frames arrive grouped per topic-mask instead
    # of in frame-staging order — per-topic FIFO holds, cross-topic order
    # within one tick does not (the same relaxation class as cross-LANE
    # reordering, which the size-bucketed rings already accept). Off by
    # default: the strict extractor keeps per-user order identical to the
    # dense plane at the cost of one radix sort over the tick's pairs.
    ragged_relaxed_order: bool = False

    def lane_shapes(self):
        """All lanes as (frame_bytes, ring_slots), sorted ascending by
        frame width (best-fit staging walks this order)."""
        return sorted(((self.frame_bytes, self.ring_slots),)
                      + tuple(self.extra_lanes))


class DevicePlane:
    # single-shard plane: inter-broker fan-out stays on the host links
    # (the mesh-group plane overrides this — peers ride ICI)
    covers_brokers = False

    def __init__(self, broker: "Broker", config: DevicePlaneConfig = None):
        self.broker = broker
        self.config = config or DevicePlaneConfig()
        c = self.config
        self.slots = UserSlots(c.num_user_slots)
        self.rings = [FrameRing(slots=s, frame_bytes=f,
                                topic_words=c.topic_words)
                      for f, s in c.lane_shapes()]
        # host mirrors — the single source of truth for device state;
        # mask shape tracks the configured topic-space width
        self._owned = np.zeros(c.num_user_slots, bool)
        self._masks = np.zeros(
            mask_mirror_shape(c.num_user_slots, c.topic_words), np.uint32)
        self._quarantine: List[int] = []   # slots awaiting step completion
        # users beyond MAX_USER_SLOTS: broadcasts must stay on the host
        # path while any exist (they'd miss device-only fan-out)
        self._unmirrored: set[bytes] = set()
        self.table_grows = 0        # times the user table doubled
        # the user dimension whose two step programs are loaded
        self._loaded_users = 0
        # mirror revision: device state re-uploads only when it changed
        # (pump_common.RevCache holds the device copy)
        self._state_rev = 0
        self._state_cache = RevCache()
        self._tmask_cache = TopicMaskCache(c.topic_words)
        # cached device-side empty lane batches + byte stubs (frame bytes
        # never ride the device on the single-shard plane: the delivery
        # DECISION comes back, payloads egress from the host ring snapshot)
        self._idle_dev_lanes = {}
        self._byte_stubs = {}
        # ragged paged delivery (ISSUE 8): the incremental per-topic page
        # index is maintained from the same observer hooks as the mirrors;
        # per tick the pump packs a walk list and the step runs the paged
        # kernel instead of the U x N sweep. Resolved once at construction
        # (env > config > router.DELIVERY_IMPL).
        import os as _os
        impl = _os.environ.get("PUSHCDN_DELIVERY_IMPL", "") or \
            c.delivery_impl
        if impl == "auto":
            from pushcdn_tpu.parallel import router as _router
            impl = _router.DELIVERY_IMPL or "dense"
        self.delivery_impl = "ragged" if impl == "ragged" else "dense"
        self._ragged = None
        self._ragged_retry_below = 0  # rebuild-retry mark post-overflow
        if self.delivery_impl == "ragged":
            from pushcdn_tpu.ops.ragged_delivery import RaggedInterest
            self._ragged = RaggedInterest(
                32 * c.topic_words, max_pages=c.ragged_max_pages)
        self.ragged_steps = 0       # ticks routed through the paged walk
        self.ragged_fallbacks = 0   # ticks that fell back to dense
        self.disabled = False
        # single-shard planes keep inter-broker traffic on host links, so
        # they never *need* overflow dialing — the attribute exists because
        # heartbeat fail-open logic reads it off any plane uniformly
        self.overflow_seen = False
        self._kick = asyncio.Event()
        # set by a stager that leaves the base lane full: it ends a pacing
        # wait (``_pace``)
        self._lane_full = asyncio.Event()
        self._task: Optional[asyncio.Task] = None
        self._step_inflight = False
        # True from the end of a ``plane.egress`` in which the pump wrote
        # streams itself (the loop stood still: the sockets hold that
        # time's frames), or after which it paces the next take, until
        # the drain before the next take is over
        self._between_steps = False
        # which step sends in the native batch off saturation, and the
        # pace of the take after it
        self._pacer = CpuPacer()
        self.steps = 0
        self.frames_staged = 0      # frames accepted into a ring
        # of those, staged while the pump drained the sockets into the
        # rings between an egress it wrote itself and its next take
        self.frames_drained = 0
        # the full ring: every ``FULL`` handed back to a stager (its
        # retries included), and the frames a ``stage_batch`` held back
        # (each then retries alone: ``_stage_with_backpressure``)
        self.stage_full_results = 0
        self.stage_full_frames = 0
        # the user loops' native pass from a receive chunk to the rings
        # (``stage_chunk``): of ``frames_staged``, the frames it staged;
        # the items it stopped in before their end (the rest went to the
        # scalar scan); and of the frames it held back on a full ring,
        # those the retry staged (``handlers._retry_full``)
        self.ingress_native_frames = 0
        self.ingress_native_stops = 0
        self.ingress_native_restaged = 0
        self._stager = None   # (stager, its arrays), made at first use
        # where the pump's wall time goes (made anew when the pump starts)
        self._account = PumpAccount()
        # the broker↔broker leg, counted by the receive loops: of
        # ``frames_staged``, those a peer's link brought
        # (``broker_receive_loop``), and the (frame, peer) sends the user
        # loops appended for the peers (``links.forward``'s ``forwards``)
        self.link_frames_staged = 0
        self.link_frames_forwarded = 0
        # monotonic time at which the rings last went from empty to
        # non-empty (None while empty): the age of the oldest staged
        # frame at the next take is ``plane.take``'s ``ring_wait_us``
        self._staged_since: Optional[float] = None
        self.messages_routed = 0
        # per-user stream hand-offs of the native egress, by how each
        # went: written by the pump on an idle link, or queued for the
        # user's writer task (senders.egress_streams tallies all three)
        self.egress_inline = 0
        self.egress_queued = 0
        # of the inline ones, those one native call sent for a step whose
        # take found the base lane full or whose sends were the period
        # (senders.egress_streams; ``_pump``); of those (and of
        # ``egress_tls_batched``), the ones of a step whose take was not
        # back-pressured
        self.egress_batched = 0
        self.egress_offsat_batched = 0
        # of the batched ones, those whose send() came back short and
        # were settled one by one; the rest were settled in one pass
        self.egress_batched_short = 0
        # of inline + queued, those over a link whose stream encrypts
        # above its socket (a user on TCP+TLS); of those, the ones the
        # pump wrote itself; and what the loop did for those inline
        # writes, in ns of ``time.monotonic_ns()`` (``egress_tls_write_us``
        # in ``describe()``): one by one the record layer and the
        # ``send()``, in the native batch the seal alone. All four stay 0
        # with plain users (senders.try_send_encoded_to_user_nowait,
        # senders._egress_batched)
        self.egress_tls = 0
        self.egress_tls_inline = 0
        self.egress_tls_write_ns = 0
        # of the inline ones, those one native call sent with the records
        # the link's own record layer sealed on the loop: a batched step's
        # (senders._egress_batched; ``egress_batched`` counts the
        # plain links' alone). Their inline writes above are those seals
        self.egress_tls_batched = 0
        # of inline + queued (and of those that failed), the hand-offs
        # whose stream is longer than one flush unit
        # (``Connection._BATCH_COALESCE_LIMIT``), which no idle link takes
        # from the pump: they go to the writers; and their bytes
        # (senders.egress_streams)
        self.egress_oversize = 0
        self.egress_oversize_bytes = 0
        self.warmup_s: Optional[float] = None

    # ---- user lifecycle (Connections observer; event-loop only) ----------

    def _ragged_set_mask(self, slot: int, topics) -> None:
        """Mirror a mask change into the ragged page index (O(changed
        topics)). Pool exhaustion falls the plane back to the dense step
        — never a dropped delivery — and once membership shrinks to half
        the overflow-time population a ``rebuild()`` is attempted (rate-
        limited by halving the retry mark on failure) so the plane
        returns to the paged walk instead of staying dense forever."""
        if self._ragged is None:
            return
        from pushcdn_tpu.parallel.frames import mask_of_topics
        self._ragged.set_mask(
            slot, mask_of_topics(topics, self.config.topic_words)
            if topics else 0)
        if not self._ragged.overflowed:
            return
        if self.delivery_impl == "ragged":
            logger.warning(
                "ragged page pool exhausted (%d pages); device plane "
                "falling back to the dense delivery step",
                self.config.ragged_max_pages)
            self.delivery_impl = "dense"
            self._ragged_retry_below = max(len(self._ragged) // 2, 1)
        elif len(self._ragged) <= self._ragged_retry_below:
            if self._ragged.rebuild():
                logger.info("ragged page index rebuilt (%d users); "
                            "resuming paged delivery", len(self._ragged))
                self.delivery_impl = "ragged"
            else:  # still too big: wait for a further halving
                self._ragged_retry_below = max(len(self._ragged) // 2, 1)

    @property
    def user_slots(self) -> int:
        """The user table's live capacity (``cdn_device_user_slots``)."""
        return self.slots.capacity

    def _grow_table(self) -> bool:
        """Double the user table and its mirrors (event loop only). A
        step in flight keeps the copies it took and the slot ids it
        carries: growth moves no binding and frees no quarantined slot.
        False at the ceiling."""
        old = self.slots.capacity
        if old >= MAX_USER_SLOTS:
            return False
        new = min(2 * old, MAX_USER_SLOTS)
        self.slots.grow(new)
        for name in ("_owned", "_masks"):
            mirror = getattr(self, name)
            grown = np.zeros((new,) + mirror.shape[1:], mirror.dtype)
            grown[:old] = mirror
            setattr(self, name, grown)
        self.table_grows += 1
        self._state_rev += 1
        logger.info("device user-slot table grew from %d to %d slots",
                    old, new)
        self._kick.set()  # the pump loads the new size's programs now
        return True

    def _step_users(self) -> int:
        """The user dimension a step runs at."""
        return effective_users(self.slots.high_water, self.slots.capacity)

    async def _load_programs(self) -> None:
        """Compile (or load) both step programs of the user dimension the
        next step runs at, unless they are loaded (the pump only). The
        connection that doubles the table is the one that moves that
        dimension past the old capacity, and it wakes the pump: the
        programs compile beside the rest of the connect storm (1.8 s at
        8,192 rows on a cold cache), not inside the first burst after it.
        A table that grows again meanwhile goes round once more."""
        while (users := self._step_users()) != self._loaded_users:
            # the walks are packed here: the page index belongs to the loop
            lanes = self._compile_lanes()
            try:
                await asyncio.to_thread(self._compile_for, users, *lanes)
            except Exception:
                # the step that needs them compiles them itself, and a
                # failure there disables the plane loudly
                logger.exception("compiling the %d-row step programs "
                                 "failed", users)
                self._loaded_users = users

    def on_user_added(self, public_key: bytes, topics) -> None:
        slots = self.slots
        if slots.slot_of(public_key) is None and slots.full \
                and not self._grow_table():
            # past the ceiling: this user is host-routed only; never fail
            # the registration over the mirror
            self._unmirrored.add(public_key)
            logger.warning(
                "device user-slot table full at its ceiling of %d slots; "
                "%d unmirrored users keep every broadcast on the host path",
                MAX_USER_SLOTS, len(self._unmirrored))
            return
        slot = slots.assign(public_key)
        self._owned[slot] = True
        self._masks[slot] = mask_row_of(topics, self.config.topic_words)
        self._ragged_set_mask(slot, topics)
        self._state_rev += 1

    def on_user_removed(self, public_key: bytes) -> None:
        self._unmirrored.discard(public_key)
        slot = self.slots.unmap(public_key)
        if slot is None:
            return
        self._owned[slot] = False
        self._masks[slot] = 0
        self._ragged_set_mask(slot, None)
        self._state_rev += 1
        # the slot index stays quarantined until the next step completes —
        # in-flight frames may still address it
        self._quarantine.append(slot)

    def on_subscription_changed(self, public_key: bytes, topics) -> None:
        slot = self.slots.slot_of(public_key)
        if slot is None:
            return
        self._masks[slot] = mask_row_of(topics, self.config.topic_words)
        self._ragged_set_mask(slot, topics)
        self._state_rev += 1

    # ---- ingress ----------------------------------------------------------

    def _idle_bypass(self, n_items: int) -> bool:
        """True when the latency regime should skip the device entirely:
        the arriving batch is small and the plane is idle — host-routing
        now beats waiting a step dispatch. Idle is exactly: the pump is
        parked on ``_kick.wait()`` with empty rings. It is not idle while
        a step is in flight, nor between an egress the pump wrote itself
        and its next take: the loop stood still all through those
        ``send()``s, a period's frames sit in the sockets, and the first
        receive loop to run would else host-route its lone frame to every
        subscriber beside the step that is about to carry the rest."""
        return (n_items <= self.config.bypass_max_items
                and not self._step_inflight
                and not self._between_steps
                and all(r.free_slots == r.slots for r in self.rings))

    def try_stage(self, message, raw: Bytes) -> StageResult:
        """Stage a decoded message's WIRE FRAME for device routing.
        INELIGIBLE ⇒ host path (too big, unknown recipient, unmirrored
        users present, or the depth-1 idle bypass); FULL ⇒ slot-credit
        backpressure, caller retries."""
        if self.disabled:
            return StageResult.INELIGIBLE
        if self._idle_bypass(1):
            return StageResult.INELIGIBLE
        frame = bytes(raw.data)
        if len(frame) > self.rings[-1].frame_bytes:
            return StageResult.INELIGIBLE
        if isinstance(message, Broadcast):
            if self._unmirrored:
                return StageResult.INELIGIBLE  # would miss unmirrored users
            mask, out_of_range = self._tmask_cache.resolve(message.topics)
            if out_of_range:
                return StageResult.INELIGIBLE  # beyond the configured space
            if mask == 0:
                return StageResult.INELIGIBLE
            ok = stage_best_fit(self.rings, len(frame),
                                lambda r: r.push_broadcast(frame, mask))
        elif isinstance(message, Direct):
            slot = self.slots.slot_of(bytes(message.recipient))
            if slot is None:
                return StageResult.INELIGIBLE  # not mirrored (cross-broker)
            ok = stage_best_fit(self.rings, len(frame),
                                lambda r: r.push_direct(frame, slot))
        else:
            return StageResult.INELIGIBLE
        if ok:
            self.frames_staged += 1
            if self._staged_since is None:
                self._staged_since = time.monotonic()
            self._kick.set()
            if not self.rings[0].free_slots:
                self._lane_full.set()
            return StageResult.STAGED
        self.stage_full_results += 1
        return StageResult.FULL

    def stage_batch(self, items) -> List[StageResult]:
        """Stage a whole receive batch in one pass: classify each
        (message, raw) pair, group the eligible frames per size lane
        (best-fit with free-slot accounting), then pack each lane's group
        with ONE ``FrameRing.push_batch`` (one C call + one copy per
        lane) instead of a per-frame Python ``_put``. Returns a
        per-item ``StageResult`` aligned with ``items``; FULL items are
        the ring-backpressure leftovers the caller retries singly."""
        results = [StageResult.INELIGIBLE] * len(items)
        if self.disabled or self._idle_bypass(len(items)):
            return results
        # (ring -> [(item_idx, frame, kind, mask, dest), ...])
        groups: dict[int, list] = {}
        free = [r.free_slots for r in self.rings]
        widest = self.rings[-1].frame_bytes
        for idx, (message, raw) in enumerate(items):
            frame = bytes(raw.data)
            if len(frame) > widest:
                continue  # INELIGIBLE
            if isinstance(message, Broadcast):
                if self._unmirrored:
                    continue
                mask, out_of_range = self._tmask_cache.resolve(
                    message.topics)
                if out_of_range or mask == 0:
                    continue
                kind, dest = KIND_BROADCAST, -1
            elif isinstance(message, Direct):
                slot = self.slots.slot_of(bytes(message.recipient))
                if slot is None:
                    continue
                kind, mask, dest = KIND_DIRECT, 0, slot
            else:
                continue
            # best-fit with credit accounting (mirrors stage_best_fit)
            placed = False
            for li, ring in enumerate(self.rings):
                if len(frame) <= ring.frame_bytes and free[li] > 0:
                    free[li] -= 1
                    groups.setdefault(li, []).append(
                        (idx, frame, kind, mask, dest))
                    placed = True
                    break
            results[idx] = StageResult.STAGED if placed else StageResult.FULL
        staged = 0
        for li, group in groups.items():
            n = self.rings[li].push_batch(
                [g[1] for g in group], [g[2] for g in group],
                [g[3] for g in group], [g[4] for g in group])
            staged += n
            for idx, *_ in group[n:]:  # raced-full leftovers
                results[idx] = StageResult.FULL
        full = results.count(StageResult.FULL)
        if full:
            self.stage_full_results += full
            self.stage_full_frames += full
        if staged:
            self.frames_staged += staged
            if self._staged_since is None:
                self._staged_since = time.monotonic()
            self._kick.set()
            if not self.rings[0].free_slots:
                self._lane_full.set()
        return results

    def _stager_args(self):
        """The chunk stager and the arrays it reads and writes, made once:
        the lanes' widths, credits and column addresses (a ring's columns
        live as long as the ring: ``take_batch`` copies them), the topics
        a frame may carry (valid, not durable, inside the mask words), and
        its per-call outputs. None where the native library is missing."""
        if self._stager is None:
            from pushcdn_tpu import native as native_mod
            fn = native_mod.chunk_stager()
            if fn is None:
                self._stager = False
                return None
            lanes = np.array(
                [[r.frame_bytes, r.slots]
                 + [a.ctypes.data for a in r.columns()] for r in self.rings],
                np.int64)
            broker = self.broker
            durable = broker.durable
            barred = durable.topics if durable is not None else ()
            topic_ok = np.zeros(256, np.uint8)
            for t in broker.run_def.topics.valid:
                if 0 <= t < 32 * self.config.topic_words and t not in barred:
                    topic_ok[t] = 1
            self._stager = (fn, lanes, np.zeros(len(self.rings), np.int32),
                            topic_ok, np.zeros(16, np.int64),
                            np.zeros(0, np.uint8))
        return self._stager or None

    def takes_chunks(self) -> bool:
        """Whether ``stage_chunk`` can run: the plane serves and the
        native library is there."""
        return not self.disabled and self._stager_args() is not None

    def stage_chunk(self, buf, offs, lens, first: int,
                    retry: bool = False) -> tuple:
        """Stage frames ``first..`` of one receive chunk (``buf`` holds
        frame ``i`` at ``offs[i]``, ``lens[i]`` bytes long) in arrival
        order, up to the first frame the native pass cannot take (a
        control or malformed frame, an unknown recipient or topic, a
        durable topic, a broadcast while unmirrored users exist, a frame
        wider than the widest lane): each frame it takes goes where
        ``stage_batch`` would put it after the scalar scan, staged or
        held back by a full ring. Returns ``(taken, status, counts)``:
        how many frames it took, per frame 1 (staged) or 2 (held back,
        ``FULL``) with 4 added for a traced frame (valid until the next
        call), and the stager's sums as a list (``native/pydecode.cpp``);
        ``(0, None, None)`` where the pass cannot run. It takes nothing
        where the plane is idle and fewer than ``bypass_max_items + 1``
        could be taken: the scalar scan decides the idle bypass on the
        whole batch.

        ``retry``: frames a full ring held back, staged in order up to the
        first that still finds no room, which ``counts[13]`` then flags
        (a ``FULL`` handed back, as ``try_stage``'s); ``taken`` is the
        frames staged. The caller keeps the idle bypass's check."""
        args = self._stager_args()
        if args is None or self.disabled:
            return 0, None, None
        fn, lanes, used, topic_ok, counts, status = args
        n = len(offs) - first
        if len(status) < n:
            status = np.zeros(max(n, 2 * len(status)), np.uint8)
            self._stager = args[:5] + (status,)
        rings = self.rings
        for li, ring in enumerate(rings):
            used[li] = ring.slots - ring.free_slots
        min_take = (self.config.bypass_max_items + 1
                    if not retry and self._idle_bypass(0) else 0)
        classes = flowclass.active_table()
        taken = fn(buf, offs, lens, first, self.slots.by_key,
                   topic_ok.ctypes.data, classes.ctypes.data,
                   self.config.topic_words, 0 if self._unmirrored else 1,
                   min_take, int(retry), lanes.ctypes.data, len(rings),
                   used.ctypes.data, status.ctypes.data, counts.ctypes.data)
        if taken < 0:
            return 0, None, None
        for li, ring in enumerate(rings):
            ring.packed(int(used[li]) - (ring.slots - ring.free_slots))
        counts = counts.tolist()
        staged, full = counts[12], counts[13]
        self.stage_full_results += full
        if not retry:
            self.stage_full_frames += full
        if staged:
            self.frames_staged += staged
            if retry:
                self.ingress_native_restaged += staged
            else:
                self.ingress_native_frames += staged
            if self._staged_since is None:
                self._staged_since = time.monotonic()
            self._kick.set()
            if not rings[0].free_slots:
                self._lane_full.set()
        return taken, status[:taken], counts

    def covered_broker_idents(self) -> set:
        """Broker identifiers whose delivery this plane covers — none for
        the single-shard plane (host links handle all peers)."""
        return set()

    # ---- the pump ---------------------------------------------------------

    async def start(self) -> None:
        logger.info("device plane: %s", self.describe())
        # compile the step off the hot path (first jit can take seconds);
        # a warm-up that raises propagates: the broker must not come up
        # as a host broker behind a --device-plane flag
        t0 = time.monotonic()
        await asyncio.to_thread(self._warmup)
        self.warmup_s = time.monotonic() - t0
        logger.info("device plane warm-up (compile + first step) took "
                    "%.2f s", self.warmup_s)
        self._task = asyncio.create_task(self._pump(), name="device-pump")

    def kernels(self) -> dict:
        """Which implementation each step shape dispatches to on this
        backend ("pallas" = compiled by Mosaic on a TPU, interpreted
        elsewhere; "xla" = the jnp twin through XLA), at the table's live
        capacity. The user dimension moves in powers of two from 64,
        which never changes the answer."""
        from pushcdn_tpu.ops.delivery_kernel import selects_pallas
        from pushcdn_tpu.ops.ragged_delivery import ragged_selects_pallas
        from pushcdn_tpu.parallel import router
        c = self.config
        dense = {f"{slots}x{width}B": slots
                 for width, slots in c.lane_shapes()}
        dense[f"latency[{c.latency_slots}]"] = c.latency_slots
        out = {name: ("pallas" if selects_pallas(
            self.slots.capacity, n, router.USE_PALLAS_DELIVERY) else "xla")
            for name, n in dense.items()}
        if self.delivery_impl == "ragged":
            out["ragged"] = ("pallas" if ragged_selects_pallas(
                router.RAGGED_USE_PALLAS) else "xla")
        return out

    def describe(self) -> dict:
        """The plane's device and state as one JSON-able dict — logged
        once at start, served under ``/debug/topology``."""
        from pushcdn_tpu import native as native_mod
        from pushcdn_tpu.parallel import runtime
        from pushcdn_tpu.proto.metrics import loop_account
        dev = runtime.device()
        return {
            "platform": dev.platform, "device_kind": dev.kind,
            "device_count": dev.count,
            "delivery_impl": self.delivery_impl,
            "kernels": self.kernels(),
            "disabled": self.disabled,
            "warmup_s": self.warmup_s,
            "steps": self.steps,
            "frames_staged": self.frames_staged,
            "frames_drained": self.frames_drained,
            "stage_full_results": self.stage_full_results,
            "stage_full_frames": self.stage_full_frames,
            "ingress_native_frames": self.ingress_native_frames,
            "ingress_native_stops": self.ingress_native_stops,
            "ingress_native_restaged": self.ingress_native_restaged,
            "link_frames_staged": self.link_frames_staged,
            "link_frames_forwarded": self.link_frames_forwarded,
            "messages_routed": self.messages_routed,
            "egress_inline": self.egress_inline,
            "egress_queued": self.egress_queued,
            "egress_batched": self.egress_batched,
            "egress_batched_short": self.egress_batched_short,
            "egress_offsat_batched": self.egress_offsat_batched,
            "egress_tls": self.egress_tls,
            "egress_tls_inline": self.egress_tls_inline,
            "egress_tls_write_us": self.egress_tls_write_ns // 1000,
            "egress_tls_batched": self.egress_tls_batched,
            "egress_oversize": self.egress_oversize,
            "egress_oversize_bytes": self.egress_oversize_bytes,
            **native_mod.egress_pool_counters(),
            "mirrored_users": len(self.slots),
            "unmirrored_users": len(self._unmirrored),
            "user_slots": self.user_slots,
            "user_high_water": self.slots.high_water,
            "table_grows": self.table_grows,
            **self._account.counters(),
            **loop_account(),
            # read when asked: whoever starts several chip-owning brokers
            # cannot look into their devices from outside
            "device_memory_peak_bytes": runtime.memory_peak_bytes(),
        }

    def _pack_walks(self, batches):
        """Pack one walk list per lane (event-loop only — the index is
        observer-mutated there). Returns None when any frame spilled
        (transient-page exhaustion) — the dense step covers that tick."""
        walks = []
        spilled = False
        for b in batches:
            w = self._ragged.pack(b.kind, b.topic_mask, b.dest, b.valid,
                                  page_round=64)
            walks.append(w)
            spilled = spilled or bool(w.spilled)
        # pack() snapshots the pool, so transient union/direct pages
        # recycle immediately (wraparound)
        self._ragged.release_transient()
        if spilled:
            self.ragged_fallbacks += 1
            return None
        return walks

    def _compile_lanes(self) -> tuple:
        """Empty lane batches, and their walks on a ragged plane: what a
        compile-only step runs on."""
        from pushcdn_tpu.parallel.frames import empty_batch
        empty = [empty_batch(r.slots, r.frame_bytes, r.topic_words)
                 for r in self.rings]
        walks = self._pack_walks(empty) \
            if self.delivery_impl == "ragged" else None
        return empty, walks

    def _warmup(self) -> None:
        self._compile_for(self._step_users(), *self._compile_lanes())

    def _compile_for(self, users: int, empty: list, walks) -> None:
        """Compile (or load) the only two specializations the pump uses
        at ``users`` rows: all lanes at full shapes (idle lanes ride
        cached device empties) and the latency-sliced base lane."""
        from pushcdn_tpu.parallel.frames import slice_batch
        c = self.config
        lat = slice_batch(empty[0], c.latency_slots)
        owned = np.zeros(users, bool)
        masks = np.zeros(mask_mirror_shape(users, c.topic_words), np.uint32)
        self._run_step(empty, owned, masks, walks=walks, compile_only=True)
        self._run_step([lat], owned, masks,
                       walks=None if walks is None else walks[:1],
                       compile_only=True)
        self._loaded_users = users

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            except Exception:
                logger.exception("device pump died during stop")

    async def _drain(self) -> int:
        """Yield to the loop until the receive loops have staged what the
        sockets held when ``plane.egress`` returned; the number of frames
        staged meanwhile. While the pump writes a step's streams itself
        the loop does not turn, so that time's frames wait in socket
        buffers, and one ``sleep(0)`` does not fetch them: the pump's
        handle is queued before the selector's reader callbacks of the
        next pass and runs first, and a frame is three passes from the
        rings (``_DRAIN_PASSES``). Without the drain the take snapshots
        rings that hold only what came during the short worker phase, and
        the rest rides the step after next. Passes, never a timer, and
        none once the base lane is full (its stagers are back-pressured:
        nothing can join the step). It changes when a frame is staged,
        not the order: one receive loop per connection stages a
        publisher's frames as they came."""
        first = self.frames_staged
        try:
            for _ in range(_DRAIN_PASSES):
                if not self.rings[0].free_slots:
                    break
                await asyncio.sleep(0)
        finally:
            self._between_steps = False
        return self.frames_staged - first

    async def _pace(self) -> int:
        """After a step whose sends left in the native batch though its
        take was not back-pressured: wait until the wall since that take
        has caught up with the CPU the process spent since it
        (``CpuPacer.owed_ns``), so that the batch's threads buy the users
        an earlier stream and not more steps; the ns waited. A take that
        finds the base lane full never waits, and a stager that fills it
        ends the wait: its publishers wait on the step. The pump calls it
        while ``_between_steps`` holds, so the idle bypass stays shut, and
        the loop turns meanwhile: what reaches a socket is staged into
        the rings, as during a long egress."""
        owed = self._pacer.owed_ns()
        if owed <= 0 or not self.rings[0].free_slots:
            return 0
        self._lane_full.clear()
        t0 = time.monotonic_ns()
        try:
            async with asyncio.timeout(owed / 1e9):
                await self._lane_full.wait()
        except asyncio.TimeoutError:
            pass
        waited = time.monotonic_ns() - t0
        self._account.paced(waited)
        return waited

    async def _pump(self) -> None:
        from pushcdn_tpu.broker.tasks.senders import egress_streams
        from pushcdn_tpu.parallel.frames import slice_batch
        c = self.config
        loop = asyncio.get_running_loop()
        gate = CoalesceGate(c.batch_window_s, c.coalesce_min_frames)
        # one sequential task: its states partition its wall time
        account = self._account = PumpAccount()
        pacer = self._pacer
        # the last step's sends left in the batch off saturation: the next
        # take waits out the CPU that step cost, and what is still owed
        # at that take is owed from it
        paced = carry = False
        while True:
            drained = 0
            if paced:
                account.enter("gate")
                paced, carry = False, True
                await self._pace()
            if self._between_steps:
                account.enter("drain")
                drained = await self._drain()
            account.enter("parked")
            await self._kick.wait()
            self._kick.clear()
            account.enter("gate")
            await self._load_programs()
            # From a parked pump: let the stagers of this pass land (a
            # burst's receive loops are already runnable). The pump's
            # handle runs BEFORE the reader callbacks the selector adds
            # to the pass it resumes in, so this fetches nothing that
            # still sits in a socket: that is ``_drain``'s work.
            await asyncio.sleep(0)
            staged = sum(r.slots - r.free_slots for r in self.rings)
            wait = gate.wait_s(staged, loop.time())
            if wait:
                # steady trickle: coalesce one window; bursts after idle
                # (the latency regime) and saturated pipelines step now
                await asyncio.sleep(wait)
            staged = sum(r.slots - r.free_slots for r in self.rings)
            if not staged:
                continue
            lat = c.latency_slots
            small = (all(r.slots - r.free_slots <= lat
                         for r in self.rings[:1])
                     and all(r.free_slots == r.slots
                             for r in self.rings[1:]))
            step = self.steps
            waited = time.monotonic() - self._staged_since
            self._staged_since = None
            u_eff = self._step_users()
            self.frames_drained += drained
            # The base lane full at the take: its stagers wait on the
            # step, so this step's length is the publishers' rate, and its
            # idle links' sends leave in the native batch. So do they off
            # saturation where the sends are the period (``CpuPacer``):
            # the take after such a step is paced, so the batch's threads
            # spend no more CPU than the one-by-one loop
            back_pressured = not self.rings[0].free_slots
            batch = back_pressured or pacer.sends_lead
            account.enter("take")
            pacer.took(carry)
            carry = False
            parked_us, gate_us, drain_us = account.since_take()
            with spans.span("plane.take", step=step, frames=staged,
                            ring_wait_us=int(waited * 1e6), users=u_eff,
                            drained=drained, parked_us=parked_us,
                            gate_us=gate_us, drain_us=drain_us):
                # snapshot mirrors + all lane rings in ONE event-loop tick
                batches_np = [r.take_batch() for r in self.rings]
                if small:
                    batches_np = [slice_batch(batches_np[0], lat)]
                owned = self._owned[:u_eff].copy()
                masks = self._masks[:u_eff].copy()
                rev = self._state_rev
                # pack the ragged walk in the SAME event-loop tick as the
                # snapshot (the page index is observer-mutated on the
                # loop; pack copies the referenced pool prefix). Overflow
                # demotes delivery_impl to "dense" (the index stays
                # maintained for the rebuild-retry path), so gate on the
                # impl, not the index
                walks = self._pack_walks(batches_np) \
                    if self.delivery_impl == "ragged" else None
                quarantined, self._quarantine = self._quarantine, []
            account.enter("worker")
            try:
                self._step_inflight = True
                try:
                    jobs = await asyncio.to_thread(
                        account.run, self._run_step, batches_np, owned,
                        masks, rev, walks)
                finally:
                    self._step_inflight = False
                account.enter("egress")
                pacer.egress_began()
                gate.stepped(loop.time())
                with spans.span("plane.egress", step=step) as sp:
                    routed, inline, queued, batched, short, tls, \
                        tls_batched, oversize = (
                            self.messages_routed, self.egress_inline,
                            self.egress_queued, self.egress_batched,
                            self.egress_batched_short, self.egress_tls,
                            self.egress_tls_batched, self.egress_oversize)
                    for streams, d2, lengths, frames in jobs:
                        if streams is not None:
                            egress_streams(self, self.broker, streams,
                                           batch)
                        else:
                            self._egress(d2, lengths, frames)
                    pacer.egress_ended(back_pressured)
                    paced = batch and not back_pressured
                    if paced:
                        self.egress_offsat_batched += (
                            self.egress_batched - batched
                            + self.egress_tls_batched - tls_batched)
                    sp.set_metadata(
                        deliveries=self.messages_routed - routed,
                        inline=self.egress_inline - inline,
                        queued=self.egress_queued - queued,
                        batched=self.egress_batched - batched,
                        short=self.egress_batched_short - short,
                        tls=self.egress_tls - tls,
                        tls_batched=self.egress_tls_batched - tls_batched,
                        oversize=self.egress_oversize - oversize)
                # the pump's own ``send()``s held the loop: drain the
                # sockets before the next take. Streams that were all
                # queued for their writers were no such hold (the loop
                # turned beside the step). A paced take keeps the idle
                # bypass shut until it is over
                self._between_steps = self.egress_inline != inline or paced
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception(
                    "device routing step failed; re-routing the batch on "
                    "the host path and disabling the device plane")
                self.disabled = True
                # frames staged (and acked STAGED) while the failing step
                # ran in the worker thread sit in the fresh rings — drain
                # them too, or they'd be lost with no fallback
                late = [r.take_batch() for r in self.rings]
                await self._host_fallback(batches_np)
                await self._host_fallback(late)
                return
            finally:
                for slot in quarantined:  # safe to recycle now
                    self.slots.free_slot(slot)

    def _run_step(self, lane_batches, owned: np.ndarray, masks: np.ndarray,
                  state_rev=None, walks=None, compile_only: bool = False):
        """Blocking device step (runs in a worker thread) against the
        snapshotted mirrors. All lanes ride one jitted program; idle lanes
        reuse cached device-side empty batches (zero H2D, and the jit key
        never depends on the traffic mix). Frame BYTES never touch the
        device: zero-width stubs stand in for the byte tensors
        (gather_bytes=False), only the delivery matrix comes back, and
        egress encodes payloads from the host ring snapshots via the
        native engine. Returns per-lane egress jobs: (EgressStreams, -, -,
        -) on the native path or (None, deliver, lengths, frames) for the
        Python fallback.

        ``walks`` (one RaggedWalk per lane) switches to the ragged paged
        step: per-tick device work scales with fan-out and the step's
        compact (frame, receiver-run) output feeds
        ``senders.egress_delivery_rows`` directly — no bool[U, N] comes
        back and Python never re-scans one. ``compile_only`` runs every
        lane regardless of traffic (warmup), returns no jobs and emits no
        profiler spans."""
        import jax.numpy as jnp
        from pushcdn_tpu import native as native_mod
        span = spans.none if compile_only else spans.span
        step = self.steps

        def build_state():
            return RouterState(
                crdt=CrdtState(
                    owners=jnp.asarray(
                        np.where(owned, 0, ABSENT).astype(np.int32)),
                    versions=jnp.asarray(owned.astype(np.uint32)),
                    identities=jnp.asarray(
                        np.where(owned, 0, ABSENT).astype(np.int32)),
                ),
                topic_masks=jnp.asarray(masks))

        def stub(n):
            st = self._byte_stubs.get(n)
            if st is None:
                st = jnp.zeros((n, 0), jnp.uint8)
                self._byte_stubs[n] = st
            return st

        def to_dev(li, b, busy):
            key = (li, b.valid.shape[0])
            if not busy:
                cached = self._idle_dev_lanes.get(key)
                if cached is not None:
                    return cached
            dev = IngressBatch(
                stub(b.valid.shape[0]), jnp.asarray(b.kind),
                jnp.asarray(b.length), jnp.asarray(b.topic_mask),
                jnp.asarray(b.dest), jnp.asarray(b.valid))
            if not busy:
                self._idle_dev_lanes[key] = dev
            return dev

        busy = [bool(b.valid.any()) for b in lane_batches]

        if walks is not None:
            # ---- ragged paged step: one walk per lane ----
            from pushcdn_tpu.ops.ragged_delivery import (
                ragged_pairs,
                ragged_pairs_grouped,
            )
            from pushcdn_tpu.parallel.router import \
                routing_step_ragged_single
            jobs = []
            routed_ragged = False
            with span("plane.h2d", step=step):
                state = self._state_cache.get(state_rev, build_state)
            for li, (b, walk) in enumerate(zip(lane_batches, walks)):
                if not busy[li] and not compile_only:
                    continue  # an idle lane has no walk entries
                with span("plane.h2d", step=step):
                    args = (to_dev(li, b, busy[li]),
                            jnp.asarray(walk.pages),
                            jnp.asarray(walk.walk_page),
                            jnp.asarray(walk.walk_frame))
                with span("plane.dispatch", step=step):
                    res = routing_step_ragged_single(state, *args)
                if compile_only:
                    res.counts.block_until_ready()
                    continue
                routed_ragged = True
                with span("plane.d2h", step=step):
                    out_user = np.asarray(res.out_user)
                with span("plane.encode", step=step):
                    if self.config.ragged_relaxed_order:
                        # per-topic FIFO only (see the config knob's docs)
                        users, frame_idx = ragged_pairs_grouped(
                            out_user, walk, num_users=len(owned))
                    else:
                        # strict: per-user order identical to the dense
                        # plane
                        users, frame_idx = ragged_pairs(
                            out_user, walk.walk_frame,
                            num_users=len(owned))
                if len(users):
                    jobs.append((None, (users, frame_idx), b.length,
                                 b.bytes_))
            if not compile_only:
                self.steps += 1
            if routed_ragged:  # warmup compile runs don't count as ticks
                self.ragged_steps += 1
            return jobs

        with span("plane.h2d", step=step):
            state = self._state_cache.get(state_rev, build_state)
            batches = tuple(to_dev(li, b, busy[li])
                            for li, b in enumerate(lane_batches))
        with span("plane.dispatch", step=step):
            result = routing_step_lanes_single(state, batches,
                                               gather_bytes=False)
        if compile_only:
            # warm-up: a step that compiles but dies on the device must
            # fail start-up, not the first real tick
            for lane in result.lanes:
                lane.deliver.block_until_ready()
            return []
        self.steps += 1
        jobs = []
        for li, lane in enumerate(result.lanes):
            if not busy[li]:
                continue  # an idle lane can't deliver: skip its D2H
            with span("plane.d2h", step=step):
                deliver = np.asarray(lane.deliver)
            b = lane_batches[li]
            with span("plane.encode", step=step):
                if not deliver.any():
                    continue
                streams = native_mod.egress_encode(deliver, b.length,
                                                   [b.bytes_])
            if streams is not None:
                jobs.append((streams, None, None, None))
            else:
                jobs.append((None, deliver, b.length, b.bytes_))
        return jobs

    def _egress(self, deliver, lengths, frames) -> None:
        """Queue delivered wire frames to local user connections —
        non-blocking and grouped per user (senders.egress_delivery_rows),
        so one slow consumer cannot stall the pump (its overflow is
        handled by the failure-is-removal policy in the sender).
        ``deliver`` is either the dense bool[U, N] matrix (scanned here —
        the Python-fallback path) or the ragged step's compact
        ``(users, frame_idx)`` pair listing, consumed as-is."""
        if isinstance(deliver, tuple):
            users, frame_idx = deliver
        else:
            users, frame_idx = np.nonzero(deliver)
        cache: dict[int, Bytes] = {}

        def frame_of(f: int) -> Bytes:
            raw = cache.get(f)
            if raw is None:
                raw = Bytes(frames[f, :lengths[f]].tobytes())
                cache[f] = raw
            return raw

        self.messages_routed += egress_delivery_rows(
            self.broker, self.slots, users, frame_idx, frame_of)
        for raw in cache.values():
            raw.release()

    async def _host_fallback(self, lane_batches) -> None:
        """Deliver batches the device failed to route, via the host path.
        Users-only on purpose: any broker-bound fan-out for these messages
        already ran on the host at staging time."""
        from pushcdn_tpu.broker.tasks.handlers import (
            handle_broadcast_message,
            handle_direct_message,
        )
        from pushcdn_tpu.proto.message import deserialize
        for b in lane_batches:
            for i in range(len(b.valid)):
                if not b.valid[i]:
                    continue
                raw = Bytes(b.bytes_[i, :b.length[i]].tobytes())
                try:
                    message = deserialize(raw.data)
                    if isinstance(message, Direct):
                        await handle_direct_message(
                            self.broker, bytes(message.recipient), raw,
                            to_user_only=True)
                    elif isinstance(message, Broadcast):
                        await handle_broadcast_message(
                            self.broker, list(message.topics), raw,
                            to_users_only=True)
                except Error:
                    pass
                finally:
                    raw.release()
