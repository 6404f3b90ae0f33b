"""Message frames as fixed-shape HBM byte tensors.

This is the device-side twin of the wire format (SURVEY.md §7 stage 1
"tensor packing" and hard-part #1): a batch of variable-length messages is
packed into a fixed ``[SLOTS, FRAME_BYTES]`` uint8 tensor plus aligned
metadata columns, so routing runs as vectorized ops instead of per-message
Python:

- ``kind``       int32[S]  — the wire kind tag (KIND_DIRECT/KIND_BROADCAST)
- ``length``     int32[S]  — payload length in bytes (0 ⇒ empty slot)
- ``topic_mask`` uint32[S] — broadcast interest bits (1 << topic)
- ``dest``       int32[S]  — direct-recipient *user slot* (-1 for broadcast)
- ``valid``      bool[S]   — slot occupancy

The byte-semaphore backpressure of the host limiter becomes slot-credit
accounting here: a ``FrameRing`` has a fixed number of slots, ``push`` fails
when full, and the host pumps only as many messages per step as there are
free slots ("block the reader, not the router" re-expressed for HBM).

User identity on device is a dense *user slot* index managed by
``UserSlots`` (public key ↔ slot), so the DirectMap twin
(pushcdn_tpu.parallel.crdt) and the router index the same space.

Messages larger than ``frame_bytes`` stay on the host path (the reference
streams up to 512 MiB through one socket frame; the device plane is for the
fan-out-heavy small/medium message regime where throughput is won).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from pushcdn_tpu.proto.error import ErrorKind, bail
from pushcdn_tpu.proto.message import KIND_BROADCAST, KIND_DIRECT

DEFAULT_FRAME_BYTES = 1024
DEFAULT_SLOTS = 1024

# The reference's topic type is a u8 (message.rs:26) — 256 possible topics.
# A topic set on device is a multi-word u32 bitmask; 8 words cover the full
# space. Rings are parameterized (``topic_words=1`` keeps the compact mask
# for deployments with ≤32 topics).
TOPIC_WORDS_FULL = 8
MAX_TOPICS = 32 * TOPIC_WORDS_FULL


def split_mask(mask: int, words: int) -> np.ndarray:
    """Split an arbitrary-width Python-int topic mask into u32 words
    (little-endian: word w holds topics 32w..32w+31)."""
    out = np.zeros(words, np.uint32)
    w = 0
    while mask and w < words:
        out[w] = mask & 0xFFFFFFFF
        mask >>= 32
        w += 1
    return out


def mask_of_topics(topics, words: int) -> int:
    """Python-int bitmask of every topic representable in ``words`` u32
    words; out-of-range topics are ignored (callers pre-check)."""
    mask = 0
    limit = 32 * words
    for t in topics:
        t = int(t)
        if t < limit:
            mask |= 1 << t
    return mask


def mask_mirror_shape(n: int, words: int):
    """Shape of an ``n``-slot topic-mask mirror/column: 1-D for the
    compact 1-word representation, [n, words] otherwise. The single place
    that encodes the dual representation rule."""
    return n if words == 1 else (n, words)


def mask_row_of(topics, words: int):
    """The mask-mirror row for a topic set: a u32 scalar when ``words`` is
    1 (compact deployments, 1-D mirrors) or a uint32[words] row otherwise —
    assignable to ``mirror[slot]`` either way."""
    mask = mask_of_topics(topics, words)
    return mask & 0xFFFFFFFF if words == 1 else split_mask(mask, words)


class UserSlots:
    """Dense user-slot allocator: public key ↔ int slot (device identity)."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._key_to_slot: Dict[bytes, int] = {}
        self._slot_to_key: List[Optional[bytes]] = [None] * capacity
        self._free: List[int] = list(range(capacity - 1, -1, -1))
        # 1 + highest slot ever assigned (lowest-free allocation order keeps
        # this tight): the device planes slice their state/delivery tensors
        # to this mark, so a 1024-slot table with 16 users costs 16-user
        # matrices, not 1024-user ones
        self.high_water = 0

    @property
    def full(self) -> bool:
        """No free slot (quarantined ones do not count as free)."""
        return not self._free

    def grow(self, capacity: int) -> None:
        """Extend the table to ``capacity`` slots. Bindings, recycled free
        slots and quarantined ones (unmapped, not yet freed) keep their
        indices; the new range goes UNDER the free list, so recycled low
        slots are still handed out first and ``high_water`` stays tight."""
        if capacity <= self.capacity:
            return
        self._slot_to_key.extend([None] * (capacity - self.capacity))
        self._free[:0] = range(capacity - 1, self.capacity - 1, -1)
        self.capacity = capacity

    def assign(self, public_key: bytes) -> int:
        slot = self._key_to_slot.get(public_key)
        if slot is not None:
            return slot
        if not self._free:
            bail(ErrorKind.EXCEEDED_SIZE,
                 f"user-slot table full ({self.capacity})")
        slot = self._free.pop()
        self._key_to_slot[public_key] = slot
        self._slot_to_key[slot] = public_key
        if slot + 1 > self.high_water:
            self.high_water = slot + 1
        return slot

    def assign_slot(self, public_key: bytes, slot: int) -> None:
        """Bind ``public_key`` to a SPECIFIC slot (multi-host planes
        allocate from statically partitioned per-shard ranges and bind
        here). The slot must be unbound."""
        if self._slot_to_key[slot] is not None:
            bail(ErrorKind.EXCEEDED_SIZE, f"slot {slot} already bound")
        self._key_to_slot[public_key] = slot
        self._slot_to_key[slot] = public_key
        if slot + 1 > self.high_water:
            self.high_water = slot + 1

    def release(self, public_key: bytes) -> None:
        slot = self.unmap(public_key)
        if slot is not None:
            self.free_slot(slot)

    def unmap(self, public_key: bytes) -> Optional[int]:
        """Drop the key↔slot mapping WITHOUT recycling the slot index —
        callers that may still have in-flight frames addressed to the slot
        quarantine it and call :meth:`free_slot` later."""
        slot = self._key_to_slot.pop(public_key, None)
        if slot is not None:
            self._slot_to_key[slot] = None
        return slot

    def free_slot(self, slot: int) -> None:
        """Return a previously :meth:`unmap`-ed slot index to the free list."""
        if self._slot_to_key[slot] is None and slot not in self._free:
            self._free.append(slot)

    def slot_of(self, public_key: bytes) -> Optional[int]:
        return self._key_to_slot.get(public_key)

    @property
    def by_key(self) -> Dict[bytes, int]:
        """The live key -> slot dict, read-only by contract: where the
        native chunk stager looks a direct's recipient up."""
        return self._key_to_slot

    def key_of(self, slot: int) -> Optional[bytes]:
        return self._slot_to_key[slot]

    def __len__(self) -> int:
        return len(self._key_to_slot)


@dataclass
class FrameBatch:
    """One step's worth of packed ingress frames (numpy, host-side; the
    router moves them to device)."""

    bytes_: np.ndarray      # uint8[S, F]
    kind: np.ndarray        # int32[S]
    length: np.ndarray     # int32[S]
    topic_mask: np.ndarray  # uint32[S]
    dest: np.ndarray        # int32[S]
    valid: np.ndarray       # bool[S]

    @property
    def num_valid(self) -> int:
        return int(self.valid.sum())


class FrameRing:
    """Fixed-capacity staging ring the host packs messages into.

    ``push_*`` returns False when no slot is free (backpressure: the caller
    keeps the message queued on the host). ``take_batch`` snapshots and
    clears up to ``slots`` frames for one router step.
    """

    def __init__(self, slots: int = DEFAULT_SLOTS,
                 frame_bytes: int = DEFAULT_FRAME_BYTES,
                 topic_words: int = 1):
        self.slots = slots
        self.frame_bytes = frame_bytes
        self.topic_words = topic_words
        self._bytes = np.zeros((slots, frame_bytes), dtype=np.uint8)
        self._kind = np.zeros(slots, dtype=np.int32)
        self._length = np.zeros(slots, dtype=np.int32)
        # [S] for the compact 1-word mask, [S, W] for wider topic spaces
        self._topic_mask = np.zeros(mask_mirror_shape(slots, topic_words),
                                    dtype=np.uint32)
        self._dest = np.full(slots, -1, dtype=np.int32)
        self._valid = np.zeros(slots, dtype=bool)
        self._next = 0
        self._used = 0
        self._empty: Optional[FrameBatch] = None
        self._mask_rows: dict = {}  # mask int -> uint32[W] word expansion

    @property
    def free_slots(self) -> int:
        return self.slots - self._used

    def columns(self) -> tuple:
        """The bytes, kind, length, topic-mask, dest and valid columns, for
        a packer that fills slots in place from the cursor (they live as
        long as the ring: ``take_batch`` copies them)."""
        return (self._bytes, self._kind, self._length, self._topic_mask,
                self._dest, self._valid)

    def packed(self, n: int) -> None:
        """Take ``n`` slots from the cursor on that such a packer filled."""
        self._used += n
        self._next += n

    def _alloc(self) -> Optional[int]:
        # Slots fill sequentially and are only freed wholesale by
        # take_batch, so the cursor always points at a free slot.
        if self._used >= self.slots:
            return None
        i = self._next
        self._next += 1
        self._used += 1
        return i

    def _put(self, i: int, payload: bytes, kind: int, topic_mask: int,
             dest: int) -> None:
        n = len(payload)
        self._bytes[i, :n] = np.frombuffer(payload, dtype=np.uint8)
        if n < self.frame_bytes:
            self._bytes[i, n:] = 0
        self._kind[i] = kind
        self._length[i] = n
        if self.topic_words == 1:
            self._topic_mask[i] = topic_mask & 0xFFFFFFFF
        else:
            self._topic_mask[i] = split_mask(topic_mask, self.topic_words)
        self._dest[i] = dest
        self._valid[i] = True

    def push_broadcast(self, payload: bytes, topic_mask: int) -> bool:
        if len(payload) > self.frame_bytes:
            bail(ErrorKind.EXCEEDED_SIZE,
                 f"payload {len(payload)} B exceeds frame slot "
                 f"{self.frame_bytes} B; use the host path")
        i = self._alloc()
        if i is None:
            return False
        self._put(i, payload, KIND_BROADCAST, topic_mask, -1)
        return True

    def push_direct(self, payload: bytes, dest_slot: int) -> bool:
        if len(payload) > self.frame_bytes:
            bail(ErrorKind.EXCEEDED_SIZE,
                 f"payload {len(payload)} B exceeds frame slot "
                 f"{self.frame_bytes} B; use the host path")
        i = self._alloc()
        if i is None:
            return False
        self._put(i, payload, KIND_DIRECT, 0, dest_slot)
        return True

    def push_batch(self, payloads: Sequence[bytes], kinds: Sequence[int],
                   tmasks: Sequence[int], dests: Sequence[int]) -> int:
        """Pack many messages in one call via the C++ framing kernel
        (native/framing.cpp, writing straight into the ring's buffers at
        the current cursor; falls back to the Python loop). Works on a
        partially-filled ring — the batch lands after any singly-pushed
        frames.

        Returns the number packed; fewer than ``len(payloads)`` means
        exactly "ring full — re-queue the rest". Oversized payloads raise
        ``ValueError`` up front (pre-filter them to the host path), so the
        return value is never ambiguous between full and unroutable.
        """
        if not (len(kinds) == len(tmasks) == len(dests) == len(payloads)):
            raise ValueError("payloads/kinds/tmasks/dests length mismatch")
        if payloads and max(map(len, payloads)) > self.frame_bytes:
            i = next(i for i, p in enumerate(payloads)
                     if len(p) > self.frame_bytes)
            raise ValueError(
                f"payload {i} is {len(payloads[i])} B > frame slot "
                f"{self.frame_bytes} B; pre-filter to the host path")
        from pushcdn_tpu import native
        start = self._next
        kinds_a = np.asarray(kinds, np.int32)
        dests_a = np.asarray(dests, np.int32)
        if self.topic_words == 1:
            try:  # C-speed for in-range masks (the ≤32-topic contract)
                tmasks_a = np.fromiter(tmasks, np.uint32,
                                       count=len(payloads))
            except (OverflowError, ValueError, TypeError):
                tmasks_a = np.asarray(
                    [m & 0xFFFFFFFF for m in tmasks], np.uint32)
        else:
            W = self.topic_words
            tmasks_a = np.zeros((len(payloads), W), np.uint32)
            # memoized word expansion: a step's masks are drawn from the
            # few distinct topic sets in flight, so expand each distinct
            # mask once (byte-exact: little-endian u32 words == the old
            # per-word shift loop) instead of W shifts per frame
            rows = self._mask_rows

            allbits = (1 << (32 * W)) - 1

            def expand(m):
                # truncate first (same semantics as the old per-word
                # shift loop): out-of-range or negative masks must not
                # turn into OverflowError from to_bytes
                m = int(m) & allbits
                row = rows.get(m)
                if row is None:
                    if len(rows) >= 4096:  # bound pathological churn
                        rows.clear()
                    row = rows[m] = np.frombuffer(
                        m.to_bytes(4 * W, "little"), np.uint32).copy()
                return row

            if not isinstance(tmasks, list):
                tmasks = list(tmasks)  # tuples/arrays get the fast path too
            first = tmasks[0] if len(tmasks) else 0
            if tmasks.count(first) == len(tmasks):
                # one publisher, one topic set — the dominant step shape:
                # a single vectorized fill instead of a row per frame
                tmasks_a[:] = expand(first)
            else:
                for i, m in enumerate(tmasks):
                    tmasks_a[i] = expand(m)
        valid_u8 = np.zeros(self.slots - start, np.uint8)
        n = native.pack_frames_into(
            list(payloads), kinds_a, tmasks_a, dests_a,
            self._bytes[start:], self._kind[start:], self._length[start:],
            self._topic_mask[start:], self._dest[start:], valid_u8)
        if n is not None:
            self._valid[start:start + n] = True
            self._used += n
            self._next += n
            return n
        # Python fallback (identical semantics)
        n = 0
        for payload, k, tm, d in zip(payloads, kinds_a, list(tmasks),
                                     dests_a):
            i = self._alloc()
            if i is None:
                break
            self._put(i, payload, int(k), int(tm), int(d))
            n += 1
        return n

    def take_batch(self) -> FrameBatch:
        """Snapshot the ring as one step's batch and clear it (slot credits
        return to the host pump). An idle ring returns a cached all-zero
        batch (batches are read-only downstream), so idle lanes cost no
        copy per step."""
        if self._used == 0:
            if self._empty is None:
                self._empty = empty_batch(self.slots, self.frame_bytes,
                                          self.topic_words)
            return self._empty
        batch = FrameBatch(
            bytes_=self._bytes.copy(), kind=self._kind.copy(),
            length=self._length.copy(), topic_mask=self._topic_mask.copy(),
            dest=self._dest.copy(), valid=self._valid.copy(),
        )
        self._valid[:] = False
        self._length[:] = 0
        self._used = 0
        self._next = 0
        return batch


@dataclass
class DirectBatch:
    """One step of per-destination-shard direct frames (axis 0 indexes the
    DESTINATION shard). The router exchanges these with one ``all_to_all``
    over the broker axis — each frame crosses ICI exactly once, to its
    owner, instead of riding the broadcast ``all_gather`` to every shard
    (SURVEY.md §2e: direct routing = point-to-point collective keyed by
    owner-device index)."""

    bytes_: np.ndarray   # uint8[B, C, F]
    length: np.ndarray   # int32[B, C]
    dest: np.ndarray     # int32[B, C] — user slot at the destination shard
    valid: np.ndarray    # bool[B, C]


class DirectBuckets:
    """Host staging for direct frames, bucketed by owner shard. The host
    knows the owner at staging time (the group's slot table), so bucketing
    costs a list-append — no device-side sort. A full bucket is per-LINK
    backpressure (only senders targeting that shard stall), the analog of
    the reference's per-connection bounded channels."""

    def __init__(self, num_shards: int, capacity: int = 64,
                 frame_bytes: int = DEFAULT_FRAME_BYTES):
        self.num_shards = num_shards
        self.capacity = capacity
        self.frame_bytes = frame_bytes
        self._bytes = np.zeros((num_shards, capacity, frame_bytes), np.uint8)
        self._length = np.zeros((num_shards, capacity), np.int32)
        self._dest = np.full((num_shards, capacity), -1, np.int32)
        self._valid = np.zeros((num_shards, capacity), bool)
        self._used = np.zeros(num_shards, np.int64)
        self._empty: Optional[DirectBatch] = None

    @property
    def total_used(self) -> int:
        return int(self._used.sum())

    @property
    def max_used(self) -> int:
        """Largest per-destination fill — the latency-slice eligibility
        check (every bucket's frames must fit the prefix slice)."""
        return int(self._used.max())

    def push(self, dest_shard: int, payload: bytes, dest_slot: int) -> bool:
        if len(payload) > self.frame_bytes:
            bail(ErrorKind.EXCEEDED_SIZE,
                 f"payload {len(payload)} B exceeds frame slot "
                 f"{self.frame_bytes} B; use the host path")
        i = int(self._used[dest_shard])
        if i >= self.capacity:
            return False  # this link is backpressured
        n = len(payload)
        self._bytes[dest_shard, i, :n] = np.frombuffer(payload, np.uint8)
        if n < self.frame_bytes:
            self._bytes[dest_shard, i, n:] = 0
        self._length[dest_shard, i] = n
        self._dest[dest_shard, i] = dest_slot
        self._valid[dest_shard, i] = True
        self._used[dest_shard] = i + 1
        return True

    def take_batch(self) -> DirectBatch:
        if self.total_used == 0:  # idle: cached zero batch, no copies
            if self._empty is None:
                self._empty = empty_direct_batch(
                    self.num_shards, self.capacity, self.frame_bytes)
            return self._empty
        batch = DirectBatch(
            bytes_=self._bytes.copy(), length=self._length.copy(),
            dest=self._dest.copy(), valid=self._valid.copy())
        self._valid[:] = False
        self._length[:] = 0
        self._dest[:] = -1
        self._used[:] = 0
        return batch


def empty_direct_batch(num_shards: int, capacity: int,
                       frame_bytes: int) -> DirectBatch:
    return DirectBatch(
        bytes_=np.zeros((num_shards, capacity, frame_bytes), np.uint8),
        length=np.zeros((num_shards, capacity), np.int32),
        dest=np.full((num_shards, capacity), -1, np.int32),
        valid=np.zeros((num_shards, capacity), bool),
    )


def slice_batch(b: FrameBatch, n: int) -> FrameBatch:
    """Prefix-slice a batch to its first ``n`` slots (views, no copies) —
    the latency-shape path: rings fill sequentially from slot 0, so when
    ``used <= n`` the prefix holds every staged frame."""
    return FrameBatch(
        bytes_=b.bytes_[:n], kind=b.kind[:n], length=b.length[:n],
        topic_mask=b.topic_mask[:n], dest=b.dest[:n], valid=b.valid[:n])


def slice_direct_batch(d: DirectBatch, n: int) -> DirectBatch:
    """Prefix-slice every destination bucket to ``n`` slots (views)."""
    return DirectBatch(
        bytes_=d.bytes_[:, :n], length=d.length[:, :n],
        dest=d.dest[:, :n], valid=d.valid[:, :n])


def stage_best_fit(lanes, size: int, push) -> bool:
    """Stage into the smallest lane a ``size``-byte frame fits, spilling to
    wider lanes when the best fit is full (a wider slot just pads more).
    ``lanes`` must be sorted ascending by ``frame_bytes``; ``push(lane)``
    does the actual staging and returns False when that lane is full.
    Returns False only when every eligible lane is full (backpressure) —
    callers pre-check ``size`` against the widest lane for eligibility."""
    for lane in lanes:
        if size <= lane.frame_bytes and push(lane):
            return True
    return False


def empty_batch(slots: int, frame_bytes: int,
                topic_words: int = 1) -> FrameBatch:
    return FrameBatch(
        bytes_=np.zeros((slots, frame_bytes), np.uint8),
        kind=np.zeros(slots, np.int32),
        length=np.zeros(slots, np.int32),
        topic_mask=np.zeros(mask_mirror_shape(slots, topic_words),
                            np.uint32),
        dest=np.full(slots, -1, np.int32),
        valid=np.zeros(slots, bool),
    )
