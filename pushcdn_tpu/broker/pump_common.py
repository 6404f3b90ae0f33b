"""Shared device-pump machinery for the single-shard plane and the mesh
group (two reviews flagged the hand-synced copies of these heuristics —
one home keeps them in lockstep):

- the adaptive coalescing gate (step immediately on bursts-after-idle and
  saturated pipelines; wait one window for a steady sub-threshold
  trickle),
- the user-table slice mark (round the slot high-water up to a power of
  two so delivery matrices, their D2H, and the egress scans pay for the
  actual population, while the jit key only moves when it doubles),
- the revision-keyed device-state cache (steady state pays zero H2D for
  the user table).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

from pushcdn_tpu.parallel.frames import mask_of_topics

# the smallest user-table slice
U_ROUND = 64


def effective_users(high_water: int, capacity: int,
                    round_to: int = U_ROUND) -> int:
    """Slice mark for the user table: the smallest power of two that
    holds ``high_water``, at least ``round_to``, clamped to capacity. A
    table that doubles when it is full crosses a mark exactly when it
    grows, so the shape a connect storm ends at is known (and can be
    compiled) half the storm before its last user arrives."""
    return min(capacity, max(round_to, 1 << (high_water - 1).bit_length()))


class CoalesceGate:
    """The latency/step-efficiency knob as one decision point.

    A step fires immediately when staged traffic reaches
    ``coalesce_min_frames`` OR when the pump has been idle (a burst after
    quiet pays no window at all); a steady trickle below the threshold
    waits one ``batch_window_s`` to amortize step dispatch.
    """

    __slots__ = ("batch_window_s", "coalesce_min_frames", "last_step_t")

    def __init__(self, batch_window_s: float, coalesce_min_frames: int):
        self.batch_window_s = batch_window_s
        self.coalesce_min_frames = coalesce_min_frames
        self.last_step_t = -1e9

    def wait_s(self, staged: int, now: float) -> float:
        """Seconds to coalesce before stepping (0 = step now)."""
        if staged and staged < self.coalesce_min_frames and \
                now - self.last_step_t < 4 * self.batch_window_s:
            return self.batch_window_s
        return 0.0

    def stepped(self, now: float) -> None:
        self.last_step_t = now


class RevCache:
    """Revision-keyed single-entry cache for device-resident state: the
    builder runs only when the revision moved (mirror mutations bump it),
    so unchanged user tables cost zero H2D per step."""

    __slots__ = ("_rev", "_value")

    def __init__(self):
        self._rev: Optional[int] = None
        self._value: Any = None

    def get(self, rev: Optional[int], build: Callable[[], Any]) -> Any:
        """Return the cached value when ``rev`` matches; otherwise build,
        and cache iff ``rev`` is not None (warmup passes None so its
        throwaway state never masks the first real upload)."""
        if rev is not None and rev == self._rev and self._value is not None:
            return self._value
        value = build()
        if rev is not None:
            self._rev = rev
            self._value = value
        return value


class TopicMaskCache:
    """Per-plane memo of topic-list -> (mask, any_out_of_range): consensus
    traffic repeats a handful of topic sets per deployment, and the
    per-message mask_of_topics loop + range scan showed up in the ingest
    profile. Bounds/eviction come from the shared BoundedTopicMemo
    policy (proto.topic)."""

    __slots__ = ("words", "_memo")

    def __init__(self, topic_words: int):
        from pushcdn_tpu.proto.topic import BoundedTopicMemo
        self.words = topic_words
        self._memo = BoundedTopicMemo()

    def resolve(self, topics):
        limit = 32 * self.words

        def compute(key):
            return (mask_of_topics(key, self.words),
                    any(int(t) >= limit for t in key))

        return self._memo.get(topics, compute)
