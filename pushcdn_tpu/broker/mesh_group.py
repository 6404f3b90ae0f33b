"""MeshBrokerGroup — N broker shards whose inter-broker traffic rides the
device mesh instead of host links.

This is the BASELINE.json north star wired into the broker runtime: each
broker in the group is one shard of a ``jax.sharding.Mesh`` over the
``"brokers"`` axis; the group pump coalesces every shard's staged frames
and runs ONE jitted ``shard_map`` routing step per tick, in which

- the inter-broker hop is the step's ``all_gather`` over ICI (replacing
  the reference's per-peer TCP writes, SURVEY.md §2e row 1-2),
- cross-shard direct routing is delivery-iff-owner (one hop, loop-free by
  construction),
- broadcast interest is the topic-bitmask kernel against the global user
  table.

Host TCP/memory broker links remain as the **fallback plane**: brokers in
a group still heartbeat/dial each other, and if a device step fails
mid-run the staged batches are re-routed over those links and the group
disables itself (fail-open to the reference's architecture; visible as
``cdn_device_plane_disabled``). A warm-up step that fails is fatal: the
member broker's ``start`` raises.

Consistency: one process = one source of truth. The group owns the GLOBAL
user-slot table and mirrors (owner shard, claim version, topic mask per
slot), mutated only on the event loop via each shard's observer facade
(:class:`MeshShardPlane`). Steps snapshot mirrors + all rings in one tick
(same discipline as the single-shard DevicePlane). In-group double
connects are authoritative at claim time: the previous owning shard's
session is kicked immediately ("user connected elsewhere"). On a real
multi-host pod each host would hold only its shard's claims and the
in-step CRDT merge would do the convergence — the device program is the
same either way (it already property-matches the host VersionedMap).
"""

from __future__ import annotations

import asyncio
import logging
import time
from dataclasses import dataclass
from typing import TYPE_CHECKING, Dict, List, Optional

import numpy as np

from pushcdn_tpu.broker.pump_common import (
    CoalesceGate,
    PumpAccount,
    RevCache,
    TopicMaskCache,
    effective_users,
)
from pushcdn_tpu.broker.tasks.senders import (
    egress_delivery_rows,
    egress_streams,
)
from pushcdn_tpu.parallel import spans
from pushcdn_tpu.parallel.crdt import ABSENT, CrdtState
from pushcdn_tpu.parallel.frames import (
    TOPIC_WORDS_FULL,
    DirectBuckets,
    FrameRing,
    UserSlots,
    mask_mirror_shape,
    mask_row_of,
    slice_batch,
    slice_direct_batch,
    stage_best_fit,
)
from pushcdn_tpu.parallel.router import (
    BROKER_AXIS,
    LaneWords,
    RouterState,
    make_mesh_lane_step,
)
from pushcdn_tpu.proto.error import Error
from pushcdn_tpu.proto.limiter import Bytes
from pushcdn_tpu.proto.message import Broadcast, Direct

if TYPE_CHECKING:
    from pushcdn_tpu.broker.broker import Broker

logger = logging.getLogger("pushcdn.broker.meshgroup")


@dataclass
class MeshGroupConfig:
    num_user_slots: int = 1024
    ring_slots: int = 256          # per shard per step (broadcast all_gather)
    direct_bucket_slots: int = 64  # per shard per DESTINATION per step
    frame_bytes: int = 2048
    # Size-bucketed lanes beyond the base lane (SURVEY.md §7 hard-part #1):
    # (frame_bytes, ring_slots, direct_bucket_slots) per entry. Frames stage
    # into the smallest lane they fit, so big proposals ride ICI without
    # padding every small ack to the widest slot.
    extra_lanes: tuple = ((16384, 32, 8),)
    # u32 words per topic mask: 8 covers the reference's whole u8 topic
    # space; 1 keeps compact masks for deployments with ≤32 topics
    topic_words: int = TOPIC_WORDS_FULL
    # Adaptive coalescing: a step fires immediately when staged traffic is
    # at least ``coalesce_min_frames`` OR the pump has been idle (burst
    # start — the latency regime pays no window at all); a steady trickle
    # below the threshold waits ``batch_window_s`` to amortize step cost.
    batch_window_s: float = 0.001
    coalesce_min_frames: int = 16
    # When everything staged fits in the first ``latency_slots`` slots of
    # every ring/bucket, the step runs on prefix-sliced shapes — a separate
    # (cached) jit specialization whose collectives move ~1/16th the bytes,
    # cutting sparse-traffic step latency several-fold.
    latency_slots: int = 8
    # Single-host groups keep frame bytes off the device altogether: all
    # shards' staged frames live in this process, so only the delivery
    # DECISION rides the mesh and with this off no frame byte reaches the
    # device (a lane's bytes leaf is a zero-width stub; a tick uploads its
    # lanes' metadata, one buffer); egress reads payloads from the host
    # ring snapshots (router.routing_step_lanes gather_bytes docs).
    # Multi-host deployments set this True.
    gather_frame_bytes: bool = False
    # One sharding-aware collective per tick: every gathered leaf (CRDT
    # state, lane metadata, direct buckets — frame bytes too when
    # ``gather_frame_bytes``) is packed into one u32 buffer and moved by a
    # single all_gather, the all_to_all folded in as gather+local-slice
    # (router._routing_step_lanes_fused). Off restores the per-array
    # collective schedule — the right call for byte-gathering multi-host
    # pods where the fused form pays B-fold redundancy on direct payloads.
    fused_collective: bool = True

    def lane_shapes(self):
        """All lanes as (frame_bytes, ring_slots, direct_bucket_slots),
        ascending by frame width."""
        return sorted(
            ((self.frame_bytes, self.ring_slots, self.direct_bucket_slots),)
            + tuple(self.extra_lanes))


class MeshShardPlane:
    """Per-broker facade: the Connections observer + staging interface for
    one shard. Duck-compatible with DevicePlane where handlers.py cares."""

    covers_brokers = True  # staged broadcasts reach mesh peers over ICI

    def __init__(self, group: "MeshBrokerGroup", shard: int):
        self.group = group
        self.shard = shard
        # what handlers.py counts on any plane whose broker has a peer
        # link (a group's only when it fails open to host links)
        self.link_frames_forwarded = 0

    # Connections observer protocol --------------------------------------
    def on_user_added(self, public_key: bytes, topics) -> None:
        self.group.claim_user(self.shard, public_key, topics)

    def on_user_removed(self, public_key: bytes) -> None:
        self.group.release_user(self.shard, public_key)

    def on_subscription_changed(self, public_key: bytes, topics) -> None:
        self.group.update_mask(self.shard, public_key, topics)

    # staging -------------------------------------------------------------
    def try_stage(self, message, raw: Bytes):
        return self.group.try_stage(self.shard, message, raw)

    def stage_batch(self, items):
        return self.group.stage_batch(self.shard, items)

    def covered_broker_idents(self) -> set:
        """Identifiers of the group's member brokers — the mesh step covers
        delivery to them, so the host path must not also forward (but MUST
        still forward to interested OUT-of-group brokers)."""
        return self.group.member_idents()

    # lifecycle (driven by the owning broker's start/stop)
    async def start(self) -> None:
        await self.group.ensure_started()

    async def stop(self) -> None:
        await self.group.on_shard_stopped(self.shard)

    def describe(self) -> dict:
        """This shard's view of the group for ``/debug/topology`` (the
        single-shard plane's twin)."""
        from pushcdn_tpu import native as native_mod
        from pushcdn_tpu.parallel import runtime
        from pushcdn_tpu.proto.metrics import loop_account
        dev = runtime.device()
        g = self.group
        return {
            "platform": dev.platform, "device_kind": dev.kind,
            "device_count": dev.count,
            "mesh_shards": g.num_shards, "shard": self.shard,
            "fused_collective": g.config.fused_collective,
            "disabled": g.disabled, "steps": g.steps,
            "frames_staged": g.frames_staged,
            "messages_routed": g.messages_routed,
            "egress_inline": g.egress_inline,
            "egress_queued": g.egress_queued,
            "egress_batched": g.egress_batched,
            "egress_batched_short": g.egress_batched_short,
            # the group batches back-pressured ticks alone and paces none
            "egress_offsat_batched": 0,
            "egress_tls": g.egress_tls,
            "egress_tls_inline": g.egress_tls_inline,
            "egress_tls_write_us": g.egress_tls_write_ns // 1000,
            "egress_tls_batched": g.egress_tls_batched,
            "egress_oversize": g.egress_oversize,
            "egress_oversize_bytes": g.egress_oversize_bytes,
            **native_mod.egress_pool_counters(),
            "h2d_puts": g.h2d_puts, "h2d_bytes": g.h2d_bytes,
            "stage_full_results": g.stage_full_results,
            "stage_full_frames": g.stage_full_frames,
            # a shard stages directs into the group's buckets, which the
            # native chunk pass does not pack: its user loops scan
            "ingress_native_frames": 0,
            "ingress_native_stops": 0,
            "ingress_native_restaged": 0,
            **g._account.counters(),
            **loop_account(),
        }

    @property
    def disabled(self) -> bool:
        return self.group.disabled

    @property
    def overflow_seen(self) -> bool:
        return self.group.overflow_seen

    @property
    def steps(self) -> int:
        return self.group.steps

    @property
    def user_slots(self) -> int:
        return self.group.slots.capacity

    @property
    def frames_staged(self) -> int:
        return self.group.frames_staged

    @property
    def messages_routed(self) -> int:
        return self.group.messages_routed


class _Members:
    """What ``tasks/senders`` asks of a broker, a user's link and its
    removal after a failed send, answered by the member that holds the
    user: an egress job spans the shards, a user's connection lives on
    one. A user whose shard has stopped, or who left mid-step, has no
    link, and its stream is dropped as a lone broker drops it."""

    def __init__(self, group: "MeshBrokerGroup"):
        self._group = group
        self.connections = self

    def _member(self, public_key: bytes) -> Optional["Broker"]:
        group = self._group
        slot = group.slots.slot_of(public_key)
        owner = ABSENT if slot is None else int(group._owner[slot])
        return None if owner == ABSENT else group.brokers[owner]

    def get_user_connection(self, public_key: bytes):
        broker = self._member(public_key)
        return None if broker is None \
            else broker.connections.get_user_connection(public_key)

    def remove_user(self, public_key: bytes, reason: str = "") -> None:
        broker = self._member(public_key)
        if broker is not None:
            broker.connections.remove_user(public_key, reason=reason)

    def update_metrics(self) -> None:
        for broker in self._group.brokers:
            if broker is not None:
                broker.update_metrics()


class MeshBrokerGroup:
    def __init__(self, mesh, config: MeshGroupConfig = None):
        self.mesh = mesh
        self.config = config or MeshGroupConfig()
        c = self.config
        self.num_shards = mesh.devices.size
        self.step_fn = make_mesh_lane_step(
            mesh, gather_bytes=self.config.gather_frame_bytes,
            fused=self.config.fused_collective)
        # every step input is placed PRE-SHARDED over the broker axis:
        # jit would otherwise silently reshard device-0-resident arrays
        # inside every call (~0.5 ms/array on an 8-device CPU mesh)
        from jax.sharding import NamedSharding, PartitionSpec
        self._sharding = NamedSharding(mesh, PartitionSpec(BROKER_AXIS))
        self.brokers: List[Optional["Broker"]] = [None] * self.num_shards
        # lane_rings[lane][shard] — size-bucketed broadcast staging
        self.lane_rings = [
            [FrameRing(slots=s, frame_bytes=f, topic_words=c.topic_words)
             for _ in range(self.num_shards)]
            for f, s, _d in c.lane_shapes()]
        # direct frames go into per-destination-shard buckets and cross the
        # mesh with one all_to_all per lane (router.DirectIngress) instead
        # of riding the broadcast all_gather to every shard
        self.lane_buckets = [
            [DirectBuckets(self.num_shards, capacity=d, frame_bytes=f)
             for _ in range(self.num_shards)]
            for f, _s, d in c.lane_shapes()]
        # global user table + mirrors (single source of truth)
        self.slots = UserSlots(c.num_user_slots)
        self._owner = np.full(c.num_user_slots, ABSENT, np.int32)
        self._claim_version = np.zeros(c.num_user_slots, np.uint32)
        # mask shape tracks the configured topic-space width
        self._masks = np.zeros(
            mask_mirror_shape(c.num_user_slots, c.topic_words), np.uint32)
        self._quarantine: List[int] = []
        # users the slot table couldn't hold, keyed to their shard so a
        # dead shard's entries can be swept (a crash fires no releases)
        self._unmirrored: Dict[bytes, int] = {}
        # dynamic membership over the static mesh (hard-part #3): a stopped
        # shard is masked dead in-step rather than re-forming the mesh
        self._liveness = np.zeros(self.num_shards, bool)
        # mirror revision: bumped on any owner/mask/liveness mutation; the
        # step thread re-uploads device state only when it changed (steady
        # state pays zero H2D for the user table)
        self._state_rev = 0
        self._state_cache = RevCache()  # (RouterState, liveness) on device
        self._tmask_cache = TopicMaskCache(c.topic_words)
        # a tick's lanes, busy or idle, cross in one buffer: per lane
        # geometry (full shapes, latency-sliced shapes) the layout and the
        # host buffer the pump packs (keying the jit cache on lane SUBSETS
        # instead would recompile per traffic mix)
        self._lane_words: Dict = {}
        # device-side constants of the lanes' bytes leaves, by shape: the
        # zero-width stub where nothing gathers bytes, else an idle
        # shard's zero block a device
        self._byte_consts: Dict = {}
        # ``jax.device_put`` calls ``_run_step`` made and the host bytes
        # it handed them (``plane.h2d``'s ``puts`` / ``bytes`` sum to
        # these; warm-up included)
        self.h2d_puts = 0
        self.h2d_bytes = 0
        self.disabled = False
        # set when traffic falls outside what the mesh step can carry —
        # heartbeats then form host links even in mesh-only deployments
        self.overflow_seen = False
        self._kick = asyncio.Event()
        self._task: Optional[asyncio.Task] = None
        self._started = False
        self._state_dirty = False  # forces a step with no staged traffic
        self.steps = 0
        self.frames_staged = 0  # frames accepted into a ring or bucket
        # the full ring (DevicePlane's twins): every ``FULL`` handed back,
        # and the frames a ``stage_batch`` held back
        self.stage_full_results = 0
        self.stage_full_frames = 0
        # where the pump's wall time goes (made anew when the pump starts)
        self._account = PumpAccount()
        # monotonic time at which rings and buckets last went from empty
        # to non-empty (None while empty): ``plane.take``'s ring_wait_us
        self._staged_since: Optional[float] = None
        self.messages_routed = 0
        # per-user stream hand-offs by how each went (DevicePlane's twin;
        # senders.egress_streams tallies all three)
        self.egress_inline = 0
        self.egress_queued = 0
        self.egress_batched = 0  # of the inline ones: by the native batch
        self.egress_batched_short = 0  # of those: settled one by one
        # of inline + queued: over a link whose stream encrypts (TCP+TLS);
        # of those: written by the pump; what those writes took, in ns
        self.egress_tls = 0
        self.egress_tls_inline = 0
        self.egress_tls_write_ns = 0
        self.egress_tls_batched = 0  # of tls inline: by the native batch
        # of all: streams over one flush unit, and their bytes
        self.egress_oversize = 0
        self.egress_oversize_bytes = 0
        self._members = _Members(self)
        # collectives traced by the most recently COMPILED step
        # specialization (router.trace_collectives delta around the call):
        # the counted one-collective-per-tick invariant, asserted by the
        # mesh dryrun tier. None until a step has traced in this process.
        self.collectives_last_trace: Optional[int] = None

    # ---- wiring ----------------------------------------------------------

    def attach(self, broker: "Broker", shard: int) -> MeshShardPlane:
        """Make ``broker`` shard ``shard`` of this group (call after
        Broker.new, before Broker.start)."""
        plane = MeshShardPlane(self, shard)
        self.brokers[shard] = broker
        self._liveness[shard] = True
        self._state_rev += 1
        broker.device_plane = plane
        broker.connections.observer = plane
        self._member_idents = None  # recompute lazily
        return plane

    def member_idents(self) -> set:
        idents = getattr(self, "_member_idents", None)
        if idents is None:
            idents = {str(b.identity) for b in self.brokers if b is not None}
            self._member_idents = idents
        return idents

    async def ensure_started(self) -> None:
        if not self._started:
            self._started = True
            # compile the step off the hot path: the first jitted shard_map
            # trace can take seconds; rings must not saturate behind it
            await asyncio.to_thread(self._warmup)
            self._task = asyncio.create_task(self._pump(), name="mesh-group-pump")

    def _warmup(self) -> None:
        # empty, right shapes: [lane][shard]
        batches = [[r.take_batch() for r in rings] for rings in self.lane_rings]
        directs = [[b.take_batch() for b in bkts] for bkts in self.lane_buckets]
        lat = self.config.latency_slots
        small = [[slice_batch(b, lat) for b in lane] for lane in batches]
        small_d = [[slice_direct_batch(d, lat) for d in lane]
                   for lane in directs]
        u0 = effective_users(0, self.config.num_user_slots)
        # compile the ONLY two specializations the pump needs at first
        # population (u_eff = first user bucket): all lanes at full
        # shapes (idle lanes ride the tick's one buffer as zeros, so
        # traffic mix never changes the jit key), and the latency-
        # sliced base lanes (sparse traffic); wider user buckets
        # compile on first growth past the mark. A failure here
        # propagates: a group that cannot step must not come up as a
        # set of host brokers behind a mesh flag.
        self._run_step(batches, directs, self._owner[:u0].copy(),
                       self._claim_version[:u0].copy(),
                       self._masks[:u0].copy())
        self._run_step(small[:1], small_d[:1], self._owner[:u0].copy(),
                       self._claim_version[:u0].copy(),
                       self._masks[:u0].copy())
        self.steps -= 2  # warmup doesn't count

    async def on_shard_stopped(self, shard: int) -> None:
        self.brokers[shard] = None
        self._liveness[shard] = False
        self._state_rev += 1
        self._member_idents = None
        # Release every slot the dead shard still owned: a crashed broker
        # never fires per-user removals, and without this sweep directs to
        # its users would be acked STAGED and dropped at the tombstone
        # (and the slot table would leak). With the mapping gone,
        # try_stage sees an unknown recipient and overflows to the host
        # path — the same "failure is an I/O error, route around it"
        # posture as the reference.
        for slot in np.nonzero(self._owner == shard)[0]:
            key = self.slots.key_of(int(slot))
            if key is not None:
                self.slots.unmap(key)
            self._owner[slot] = ABSENT
            self._claim_version[slot] += 1
            self._masks[slot] = 0
            self._quarantine.append(int(slot))
        # unmirrored users of the dead shard would otherwise pin every
        # broadcast to the host path forever
        for key in [k for k, s in self._unmirrored.items() if s == shard]:
            del self._unmirrored[key]
        # wake the pump even with no staged traffic: the tombstoned release
        # must reach the device CRDT, already-staged frames to the dead
        # shard must be flushed (dropped at the tombstone), and the
        # quarantined slots must return to the free list
        self._state_dirty = True
        self._kick.set()
        if all(b is None for b in self.brokers) and self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            except Exception:
                logger.exception("mesh-group pump died during stop")
            self._task = None
            self._started = False

    # ---- mirrors (event-loop only) ---------------------------------------

    def claim_user(self, shard: int, public_key: bytes, topics) -> None:
        try:
            slot = self.slots.assign(public_key)
        except Error:
            self._unmirrored[public_key] = shard
            logger.warning("mesh-group slot table full; %d unmirrored",
                           len(self._unmirrored))
            return
        prev = int(self._owner[slot])
        if prev != ABSENT and prev != shard:
            # in-group double connect: kick the old session immediately
            # (the host CRDT handles out-of-group brokers)
            old = self.brokers[prev]
            if old is not None and old.connections.has_user(public_key):
                logger.info("user connected elsewhere in group (shard %d -> %d)",
                            prev, shard)
                old.connections.remove_user(
                    public_key, reason="user connected elsewhere")
                # removal via the old shard's observer released the slot;
                # re-assign for the new owner (the freed slot is quarantined
                # until the next step, so a full table can fail here too)
                try:
                    slot = self.slots.assign(public_key)
                except Error:
                    self._unmirrored[public_key] = shard
                    logger.warning(
                        "mesh-group slot table full after in-group kick; "
                        "%d unmirrored", len(self._unmirrored))
                    return
        self._owner[slot] = shard
        self._claim_version[slot] += 1
        self._masks[slot] = mask_row_of(topics, self.config.topic_words)
        self._state_rev += 1

    def release_user(self, shard: int, public_key: bytes) -> None:
        self._unmirrored.pop(public_key, None)
        slot = self.slots.slot_of(public_key)
        if slot is None or int(self._owner[slot]) != shard:
            return  # not ours (already taken over by another shard)
        self.slots.unmap(public_key)
        self._owner[slot] = ABSENT
        self._claim_version[slot] += 1
        self._masks[slot] = 0
        self._quarantine.append(slot)
        self._state_rev += 1

    def update_mask(self, shard: int, public_key: bytes, topics) -> None:
        slot = self.slots.slot_of(public_key)
        if slot is not None and int(self._owner[slot]) == shard:
            self._masks[slot] = mask_row_of(topics, self.config.topic_words)
            self._state_rev += 1

    # ---- staging ----------------------------------------------------------

    def _overflow(self):
        """Traffic the mesh step can't carry must ride host links: flag it
        and wake every member's heartbeat so those links form promptly."""
        from pushcdn_tpu.broker.staging import StageResult
        if not self.overflow_seen:
            self.overflow_seen = True
            logger.info("mesh-group overflow traffic; host links requested")
        for b in self.brokers:
            if b is not None:
                b.host_links_kick.set()
        return StageResult.INELIGIBLE

    def _direct_route_info(self, recipient: bytes):
        """Resolve a direct recipient to (device slot, owner shard), or
        None when the mesh can't carry it (unknown/absent recipient — the
        host path's job). The multi-host group overrides this with the
        statically partitioned slot space + the discovery directory."""
        slot = self.slots.slot_of(recipient)
        if slot is None:
            return None
        owner = int(self._owner[slot])
        if owner == ABSENT:
            return None
        return slot, owner

    def try_stage(self, shard: int, message, raw: Bytes):
        from pushcdn_tpu.broker.staging import StageResult
        if self.disabled:
            return StageResult.INELIGIBLE
        frame = bytes(raw.data)
        if len(frame) > self.lane_rings[-1][shard].frame_bytes:
            return self._overflow()
        if isinstance(message, Broadcast):
            if self._unmirrored:
                return self._overflow()
            mask, out_of_range = self._tmask_cache.resolve(message.topics)
            if out_of_range:
                return self._overflow()
            if mask == 0:
                return StageResult.INELIGIBLE  # no valid topics: no-op send
            ok = stage_best_fit(
                [rings[shard] for rings in self.lane_rings], len(frame),
                lambda r: r.push_broadcast(frame, mask))
        elif isinstance(message, Direct):
            info = self._direct_route_info(bytes(message.recipient))
            if info is None:
                # outside the group: legitimately the host path's job
                return self._overflow()
            slot, owner = info
            # one-hop ICI path: bucket by owner shard for the all_to_all
            ok = stage_best_fit(
                [bkts[shard] for bkts in self.lane_buckets], len(frame),
                lambda b: b.push(owner, frame, slot))
        else:
            return StageResult.INELIGIBLE
        if ok:
            self.frames_staged += 1
            if self._staged_since is None:
                self._staged_since = time.monotonic()
            self._kick.set()
            return StageResult.STAGED
        self.stage_full_results += 1
        return StageResult.FULL

    def stage_batch(self, shard: int, items):
        """Batch staging for one member shard: broadcasts are grouped per
        size lane and packed with ONE ``FrameRing.push_batch`` per lane
        (the C framing kernel, multi-word masks included); directs keep
        the per-frame owner-bucket push (each lands in a different
        [dest][slot] cell, so there is no contiguous batch to pack).
        Returns per-item ``StageResult``s aligned with ``items``."""
        from pushcdn_tpu.broker.staging import StageResult
        results = [StageResult.INELIGIBLE] * len(items)
        if self.disabled:
            return results
        groups: dict[int, list] = {}
        rings = [lane[shard] for lane in self.lane_rings]
        free = [r.free_slots for r in rings]
        widest = rings[-1].frame_bytes
        staged = 0
        for idx, (message, raw) in enumerate(items):
            frame = bytes(raw.data)
            if len(frame) > widest:
                self._overflow()
                continue
            if isinstance(message, Broadcast):
                if self._unmirrored:  # short-circuit before mask work
                    self._overflow()
                    continue
                mask, out_of_range = self._tmask_cache.resolve(
                    message.topics)
                if out_of_range:
                    self._overflow()
                    continue
                if mask == 0:
                    continue  # no valid topics: no-op send
                placed = False
                for li, ring in enumerate(rings):
                    if len(frame) <= ring.frame_bytes and free[li] > 0:
                        free[li] -= 1
                        groups.setdefault(li, []).append((idx, frame, mask))
                        placed = True
                        break
                results[idx] = (StageResult.STAGED if placed
                                else StageResult.FULL)
            elif isinstance(message, Direct):
                info = self._direct_route_info(bytes(message.recipient))
                if info is None:
                    self._overflow()
                    continue
                slot, owner = info
                ok = stage_best_fit(
                    [bkts[shard] for bkts in self.lane_buckets], len(frame),
                    lambda b: b.push(owner, frame, slot))
                results[idx] = (StageResult.STAGED if ok
                                else StageResult.FULL)
                staged += ok
        from pushcdn_tpu.proto.message import KIND_BROADCAST
        for li, group in groups.items():
            n = rings[li].push_batch(
                [g[1] for g in group],
                [KIND_BROADCAST] * len(group),
                [g[2] for g in group],
                [-1] * len(group))
            staged += n
            for idx, *_ in group[n:]:
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
        return results

    # ---- the pump ---------------------------------------------------------

    def _staged_total(self) -> int:
        return (sum(r.slots - r.free_slots
                    for rings in self.lane_rings for r in rings)
                + sum(b.total_used
                      for bkts in self.lane_buckets for b in bkts))

    def _back_pressured(self) -> bool:
        """Whether a stager of the base lane waits on this tick: a live
        shard's base ring has no free slot, or one of its base direct
        buckets stands at its capacity (``DirectBuckets.push`` refuses).
        The tick is lockstep, one period for all shards, so the
        observation is the group's: while any shard's publishers wait on
        the tick, the length of every shard's sends is their rate. The
        wide lanes do not count, as ``DevicePlane`` counts its base ring
        only."""
        return any(
            live and (not ring.free_slots
                      or buckets.max_used >= buckets.capacity)
            for live, ring, buckets in zip(
                self._liveness, self.lane_rings[0], self.lane_buckets[0]))

    async def _pump(self) -> None:
        c = self.config
        loop = asyncio.get_running_loop()
        gate = CoalesceGate(c.batch_window_s, c.coalesce_min_frames)
        # one sequential task: its states partition its wall time
        account = self._account = PumpAccount()
        while True:
            account.enter("parked")
            await self._kick.wait()
            self._kick.clear()
            account.enter("gate")
            # one yield so every stager woken in this tick lands first
            await asyncio.sleep(0)
            staged = self._staged_total()
            wait = gate.wait_s(staged, loop.time())
            if wait:
                # steady trickle below the coalesce threshold: wait one
                # window. A burst after idle (latency regime) and a
                # saturated pipeline both step immediately.
                await asyncio.sleep(wait)
                staged = self._staged_total()
            if not self._state_dirty and staged == 0:
                continue
            self._state_dirty = False
            # prefix-slice to the latency shapes when everything staged
            # fits the base lanes' first ``latency_slots`` slots and the
            # extra lanes are idle (collectives then move ~ring/lat× fewer
            # bytes; one extra cached jit specialization)
            lat = c.latency_slots
            small = (all(r.slots - r.free_slots <= lat
                         for r in self.lane_rings[0])
                     and all(b.max_used <= lat
                             for b in self.lane_buckets[0])
                     and all(r.free_slots == r.slots
                             for rings in self.lane_rings[1:] for r in rings)
                     and all(b.total_used == 0
                             for bkts in self.lane_buckets[1:] for b in bkts))
            step = self.steps
            waited = (0.0 if self._staged_since is None
                      else time.monotonic() - self._staged_since)
            self._staged_since = None
            back_pressured = self._back_pressured()
            account.enter("take")
            parked_us, gate_us, drain_us = account.since_take()
            with spans.span("plane.take", step=step, frames=staged,
                            ring_wait_us=int(waited * 1e6),
                            parked_us=parked_us, gate_us=gate_us,
                            drain_us=drain_us):
                # one-tick snapshot: all lanes' rings + buckets + mirrors
                batches = [[r.take_batch() for r in rings]
                           for rings in self.lane_rings]
                directs = [[b.take_batch() for b in bkts]
                           for bkts in self.lane_buckets]
                if small:
                    batches = [[slice_batch(b, lat) for b in batches[0]]]
                    directs = [[slice_direct_batch(d, lat)
                                for d in directs[0]]]
                # slice the user table to its high-water mark (rounded up
                # so the jit key only moves every ``u_round`` users):
                # delivery matrices, their D2H, and the egress scans all
                # shrink with the actual population instead of paying for
                # empty slots
                u_eff = effective_users(self.slots.high_water,
                                        self.config.num_user_slots)
                owner = self._owner[:u_eff].copy()
                versions = self._claim_version[:u_eff].copy()
                masks = self._masks[:u_eff].copy()
                liveness = self._liveness.copy()
                rev = self._state_rev
                quarantined, self._quarantine = self._quarantine, []
            account.enter("worker")
            try:
                egress_jobs = await asyncio.to_thread(
                    account.run, self._run_step, batches, directs, owner,
                    versions, masks, liveness, rev, step)
                account.enter("egress")
                gate.stepped(loop.time())
                with spans.span("plane.egress", step=step) as sp:
                    routed, inline, queued, batched, short, tls, \
                        tls_batched, oversize = (
                            self.messages_routed, self.egress_inline,
                            self.egress_queued, self.egress_batched,
                            self.egress_batched_short, self.egress_tls,
                            self.egress_tls_batched, self.egress_oversize)
                    for streams, d2, lengths, frames in egress_jobs:
                        if streams is not None:
                            egress_streams(self, self._members, streams,
                                           back_pressured)
                        else:
                            self._egress_py(self._members, d2, lengths,
                                            frames)
                    sp.set_metadata(
                        deliveries=self.messages_routed - routed,
                        inline=self.egress_inline - inline,
                        queued=self.egress_queued - queued,
                        batched=self.egress_batched - batched,
                        short=self.egress_batched_short - short,
                        tls=self.egress_tls - tls,
                        tls_batched=self.egress_tls_batched - tls_batched,
                        oversize=self.egress_oversize - oversize)
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception(
                    "mesh-group step failed; re-routing batches over host "
                    "links and disabling the group")
                self.disabled = True
                # frames staged (and acked as STAGED) while the failing step
                # ran in the worker thread sit in the fresh rings — drain
                # them too, or they'd be lost with no fallback
                late = [[r.take_batch() for r in rings]
                        for rings in self.lane_rings]
                late_d = [[b.take_batch() for b in bkts]
                          for bkts in self.lane_buckets]
                for lane in batches + late:
                    await self._host_fallback(lane)
                for lane in directs + late_d:
                    await self._host_fallback_direct(lane)
                return
            finally:
                for slot in quarantined:
                    self.slots.free_slot(slot)

    def _run_step(self, batches, directs, owner, versions, masks,
                  liveness=None, state_rev=None, step: Optional[int] = None):
        """Blocking multi-shard device step (worker thread). ``batches`` and
        ``directs`` are [lane][shard] host snapshots; all lanes ride ONE
        jitted shard_map program with one shared CRDT merge.

        A tick uploads what its step reads, once: every lane's metadata
        (busy or idle: an idle lane is a few KiB of zeros, so the jit key
        never depends on the traffic mix) is packed into one ``[B, W]``
        u32 buffer (:class:`router.LaneWords`) and crosses in one
        ``device_put``. No frame byte crosses when ``gather_frame_bytes``
        is off: a lane's bytes leaf is a cached zero-width stub, and
        egress payloads come from the HOST snapshots. The device user
        table is re-uploaded only when ``state_rev`` moved (steady state
        pays zero H2D for state). The step returns one egress job a busy
        lane, each either a native :class:`native.EgressStreams` (encoded
        right here, off the event loop) or the Python-fallback (deliver,
        lengths, frames) triple.

        ``step`` is the tick number the profiler spans carry; without one
        (the compile-only warm-up) the step emits no spans."""
        import jax
        span = spans.none if step is None else spans.span
        B = self.num_shards
        live = (np.ones(B, bool) if liveness is None else liveness)
        puts, nbytes = self.h2d_puts, self.h2d_bytes

        def put(a, where=self._sharding):
            self.h2d_puts += 1
            self.h2d_bytes += a.nbytes
            return jax.device_put(a, where)

        def build_state():
            # every shard's state row is the (shared) global view; on real
            # multi-host pods these rows diverge and the in-step merge
            # converges them — the device program is identical
            owners_b = np.broadcast_to(owner, (B,) + owner.shape)
            versions_b = np.broadcast_to(versions, (B,) + versions.shape)
            masks_b = np.broadcast_to(masks, (B,) + masks.shape)
            return (RouterState(
                crdt=CrdtState(put(owners_b),
                               put(versions_b),
                               put(owners_b)),  # identity = shard
                topic_masks=put(masks_b)),
                put(np.broadcast_to(live, (B, B))))

        def const(shape, build):
            got = self._byte_consts.get(shape)
            if got is None:
                got = self._byte_consts[shape] = build()
            return got

        def put_rows(lane):
            """``gather_frame_bytes`` only: assemble the [B, ...] byte
            tensor per device. Busy shards H2D their own block; idle
            shards reuse a cached device-side zero block (their ``valid``
            masks are False, so stale content can never deliver): with
            one busy shard this moves 1/B of the bytes a full stack
            would."""
            devices = self.mesh.devices.reshape(-1)
            block = (1,) + lane[0].bytes_.shape
            zeros = const(block, lambda: [put(np.zeros(block, np.uint8), d)
                                          for d in devices])
            return jax.make_array_from_single_device_arrays(
                (B,) + block[1:], self._sharding,
                [put(snap.bytes_[None], devices[i]) if snap.valid.any()
                 else zeros[i] for i, snap in enumerate(lane)])

        def bytes_leaf(lane):
            """A lane's ``frame_bytes`` leaf: the bytes where the step
            gathers them, else a zero-width stub of the lane's geometry
            (nothing reads it; what ``DevicePlane`` does on one chip)."""
            if self.config.gather_frame_bytes:
                return put_rows(lane)
            stub = (B,) + lane[0].bytes_.shape[:-1] + (0,)
            return const(stub, lambda: put(np.zeros(stub, np.uint8)))

        busy_b = [any(b.valid.any() for b in lane) for lane in batches]
        busy_d = [any(d.valid.any() for d in lane) for lane in directs]
        geometry = (tuple(lane[0].valid.shape[0] for lane in batches),
                    tuple(lane[0].valid.shape for lane in directs))
        with span("plane.h2d", step=step) as sp:
            state, live_dev = self._state_cache.get(state_rev, build_state)
            packed = self._lane_words.get(geometry)
            if packed is None:
                layout = LaneWords(*geometry, self._masks.shape[1:])
                packed = self._lane_words[geometry] = (
                    layout, np.zeros((B, layout.width), np.uint32))
            layout, buf = packed
            # the step that read this buffer last has finished (its
            # decisions were read back), so its words can be overwritten
            layout.pack(buf, batches, directs)
            words = put(buf)
            lane_bytes = tuple(bytes_leaf(lane) for lane in batches)
            direct_bytes = tuple(bytes_leaf(lane) for lane in directs)
            sp.set_metadata(puts=self.h2d_puts - puts,
                            bytes=self.h2d_bytes - nbytes)
        from pushcdn_tpu.parallel import router as router_mod
        before = router_mod.trace_collectives()
        with span("plane.dispatch", step=step):
            result = self.step_fn(state, lane_bytes, direct_bytes,
                                  live_dev, words)
        traced = router_mod.trace_collectives() - before
        if traced:  # this call compiled a fresh specialization
            self.collectives_last_trace = traced
        self.steps += 1
        if not (any(busy_b) or any(busy_d)):
            # nothing to read back (warm-up, membership-only tick): wait
            # for the step anyway, so a device failure raises here and
            # not at some later tick's readback
            with span("plane.d2h", step=step):
                jax.block_until_ready(result.evictions)
        # ---- egress prep: decisions from the mesh, payloads from host ----
        # (idle lanes can't deliver: skip their D2H entirely)
        jobs = []
        for li, l in enumerate(result.lanes):
            if not busy_b[li]:
                continue
            with span("plane.d2h", step=step):
                deliver = np.asarray(l.deliver)      # bool[B, U, N]
                if self.config.gather_frame_bytes:
                    lengths = np.asarray(l.gathered_length[0])
                    blocks = [np.asarray(l.gathered_bytes[0])]
                else:
                    lane = batches[li]
                    lengths = np.concatenate([b.length for b in lane])
                    blocks = [b.bytes_ for b in lane]
            jobs.append((deliver, lengths, blocks, None))
        for li, l in enumerate(result.direct_lanes):
            if not busy_d[li]:
                continue
            with span("plane.d2h", step=step):
                deliver = np.asarray(l.deliver)      # bool[B, U, B*C]
                if self.config.gather_frame_bytes:
                    # all_to_all output DIFFERS per shard (unlike the
                    # broadcast all_gather): each shard's received
                    # bytes/lengths must pair with that shard's own
                    # delivery mask
                    lengths = np.asarray(l.gathered_length)  # [B, B*C]
                    blocks = np.asarray(l.gathered_bytes)    # [B, B*C, F]
                    jobs.append((deliver, lengths, blocks, "per-shard"))
                else:
                    # the all_to_all transposes buckets: shard j receives,
                    # from each source shard, that source's bucket FOR j
                    lane = directs[li]
                    jobs.append((deliver, None, None, lane))
        with span("plane.encode", step=step):
            return self._encode_jobs(jobs)

    def _encode_jobs(self, jobs) -> list:
        """One egress job a busy lane from one step's read-back decisions
        (worker thread; the step's ``plane.encode`` span). A shard
        delivers to the users it owns and a user has one owner, so the
        shards' matrices of a lane hold no user twice: they encode as ONE
        matrix, one stream a user whichever member holds it
        (:class:`_Members` finds the link), and a back-pressured tick's
        sends leave in one native batch a lane, not one a shard."""
        from pushcdn_tpu import native as native_mod
        B = self.num_shards
        out = []
        for deliver, lengths, blocks, direct_lane in jobs:
            if direct_lane is None:
                # every shard decided over the same gathered frames
                d2 = deliver.any(axis=0)
            else:
                # the all_to_all gave each shard frames of its own: its
                # columns stand beside the other shards'
                d2 = deliver.transpose(1, 0, 2).reshape(deliver.shape[1], -1)
                if direct_lane == "per-shard":
                    lengths, blocks = lengths.reshape(-1), list(blocks)
                else:
                    lengths = np.concatenate(
                        [direct_lane[src].length[shard]
                         for shard in range(B) for src in range(B)])
                    blocks = [direct_lane[src].bytes_[shard]
                              for shard in range(B) for src in range(B)]
            if not d2.any():
                continue
            streams = native_mod.egress_encode(d2, lengths, blocks)
            if streams is not None:
                out.append((streams, None, None, None))
            else:  # no native library: per-frame Python fallback
                out.append((None, d2, lengths, np.concatenate(blocks)))
        return out

    def _egress_py(self, broker, deliver2, lengths, frames) -> None:
        """Per-frame fallback egress of one job (native lib absent)."""
        users, frame_idx = np.nonzero(deliver2)
        cache: Dict[int, Bytes] = {}

        def frame_of(f: int) -> Bytes:
            raw = cache.get(f)
            if raw is None:
                raw = Bytes(frames[f, :lengths[f]].tobytes())
                cache[f] = raw
            return raw

        self.messages_routed += egress_delivery_rows(
            broker, self.slots, users, frame_idx, frame_of)
        for raw in cache.values():
            raw.release()

    async def _host_fallback(self, batches) -> None:
        """Re-route every staged frame over the host plane (brokers keep
        their TCP/memory mesh links as backup)."""
        from pushcdn_tpu.broker.tasks.handlers import (
            handle_broadcast_message,
            handle_direct_message,
        )
        from pushcdn_tpu.proto.message import deserialize
        members = self.member_idents()
        for shard, b in enumerate(batches):
            broker = self.brokers[shard]
            if broker is None:
                continue
            # Staged broadcasts were ALREADY forwarded to interested
            # out-of-group brokers at staging time (the stage-time exclude
            # set covers only group members) — re-forwarding here would
            # deliver those subscribers a second copy. The fallback only
            # owes what the failed step owed: local users + group members.
            out_of_group = frozenset(
                ident for ident in broker.connections.all_broker_identifiers()
                if ident not in members)
            for i in range(len(b.valid)):
                if not b.valid[i]:
                    continue
                raw = Bytes(b.bytes_[i, :b.length[i]].tobytes())
                try:
                    message = deserialize(raw.data)
                    if isinstance(message, Direct):
                        await handle_direct_message(
                            broker, bytes(message.recipient), raw,
                            to_user_only=False)
                    elif isinstance(message, Broadcast):
                        await handle_broadcast_message(
                            broker, list(message.topics), raw,
                            to_users_only=False,
                            exclude_brokers=out_of_group)
                except Error:
                    pass
                finally:
                    raw.release()

    async def _host_fallback_direct(self, directs) -> None:
        """Re-route staged direct-bucket frames over the host plane (the
        recipient is in the wire frame; bucket geometry doesn't matter)."""
        from pushcdn_tpu.broker.tasks.handlers import handle_direct_message
        from pushcdn_tpu.proto.message import deserialize
        for shard, d in enumerate(directs):
            broker = self.brokers[shard]
            if broker is None:
                continue
            dests, idx = np.nonzero(d.valid)
            for b_dest, i in zip(dests.tolist(), idx.tolist()):
                raw = Bytes(d.bytes_[b_dest, i, :d.length[b_dest, i]].tobytes())
                try:
                    message = deserialize(raw.data)
                    if isinstance(message, Direct):
                        await handle_direct_message(
                            broker, bytes(message.recipient), raw,
                            to_user_only=False)
                except Error:
                    pass
                finally:
                    raw.release()

