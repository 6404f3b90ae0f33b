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
  the user table),
- the pump's state account (where the one sequential pump task spends its
  wall time, as cumulative microseconds in ``describe()``),
- the CPU pacer (``DevicePlane`` alone: which step sends in the native
  batch off saturation, and how long the take after it waits).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

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


class PumpAccount:
    """Where the pump's wall time goes, as cumulative microseconds.

    The pump is one sequential task, so its states partition its wall
    time: ``enter(state)`` adds the time since the last call to the state
    that ends there, and the six counters sum to the pump's lifetime
    within the state that is still open. Plain ints that only grow, on
    ``time.monotonic_ns()`` (the clock of the benchmark's marks): the
    difference between two ``describe()`` calls is the interval's, the
    sum over brokers the deployment's. ``DevicePlane`` alone has a drain;
    the group's ``drain`` stays 0.

    ====================  ================================================
    ``pump_parked_us``    awaiting ``_kick`` with nothing staged
    ``pump_gate_us``      ``_load_programs``, the ``sleep(0)`` that lets
                          the pass's stagers land, the coalescing
                          ``sleep(wait)``, ``DevicePlane._pace``
    ``pump_drain_us``     ``DevicePlane._drain``
    ``pump_take_us``      the ``plane.take`` section
    ``pump_worker_us``    ``await asyncio.to_thread(...)`` of the step:
                          the worker's wall and both thread hops
    ``pump_egress_us``    the ``plane.egress`` section
    ``worker_busy_us``    the step's wall measured on the worker thread
                          (:meth:`run`), so ``pump_worker_us`` less this
                          is the two hops
    ``pump_paced_us``     of ``pump_gate_us``, the waits of ``_pace``
                          (:meth:`paced`); 0 in the group, which never
                          paces
    ``pump_paced_steps``  the takes that waited there
    ====================  ================================================
    """

    STATES = ("parked", "gate", "drain", "take", "worker", "egress")
    __slots__ = ("us", "worker_busy_us", "paced_us", "paced_steps",
                 "_state", "_since", "_taken")

    def __init__(self):
        self.us: Dict[str, int] = dict.fromkeys(self.STATES, 0)
        self.worker_busy_us = 0
        self.paced_us = 0
        self.paced_steps = 0
        self._state = "parked"
        self._since = time.monotonic_ns()
        self._taken = (0, 0, 0)

    def enter(self, state: str) -> None:
        """The open state ends now and ``state`` begins. Whole
        microseconds are credited and the remainder carried, so nothing
        is lost to rounding however short the states."""
        us = (time.monotonic_ns() - self._since) // 1000
        self.us[self._state] += us
        self._since += us * 1000
        self._state = state

    def since_take(self) -> Tuple[int, int, int]:
        """``(parked_us, gate_us, drain_us)`` since the last call: what
        lay between the egress before and the take that asks, the stats
        that close a traced period (take + worker + egress + these three
        = take to take)."""
        now = (self.us["parked"], self.us["gate"], self.us["drain"])
        last, self._taken = self._taken, now
        return tuple(a - b for a, b in zip(now, last))

    def run(self, step: Callable[..., Any], *args) -> Any:
        """``step(*args)`` on the calling thread (the worker), its wall
        added to ``worker_busy_us``."""
        t0 = time.monotonic_ns()
        try:
            return step(*args)
        finally:
            self.worker_busy_us += (time.monotonic_ns() - t0) // 1000

    def paced(self, ns: int) -> None:
        """A take waited ``ns`` inside the open ``gate`` state."""
        self.paced_us += ns // 1000
        self.paced_steps += 1

    def counters(self) -> Dict[str, int]:
        """The nine counters under their names in ``describe()``."""
        out = {f"pump_{state}_us": us for state, us in self.us.items()}
        out["worker_busy_us"] = self.worker_busy_us
        out["pump_paced_us"] = self.paced_us
        out["pump_paced_steps"] = self.paced_steps
        return out


class CpuPacer:
    """Which step of ``DevicePlane``'s pump sends in the native batch
    though its take was not back-pressured, and how long the take after
    such a step waits: the routing process's CPU against the wall, on
    injectable clocks (``time.process_time_ns``, the process's user and
    system time over all its threads, as ``broker_cpu_us_per_delivery``
    reads it; ``time.monotonic_ns``).

    One by one, a step's sends cost the event loop's core, so the wall
    they take is their CPU and the pump steps at most once per step's
    CPU. In the batch the same sends leave over several threads in a
    fraction of that wall (``native.send_batch``); a pump that stepped
    again at once would send to every user again sooner, and the process
    would spend more CPU a delivery. So after such a step the next take
    waits until the wall since this take has caught up with the CPU
    spent since it (:meth:`owed_ns`), and a take after such a wait
    carries what it still owes (:meth:`took`): the process spends no
    more than the one core it spent one by one, and each user's stream
    leaves sooner after the decision.

    ``sends_lead`` is the pump's observation that the sends are the
    period: over the last step that was not back-pressured, the CPU its
    egress spent exceeded the wall from its take to its egress (the
    snapshot and the worker). CPU and not wall, since the batch shortens
    the egress's wall and not its CPU: once engaged, the observation
    stays where it was. A back-pressured step leaves it False: its length
    is its publishers' rate, so a lull after it (a step whose take found
    room) goes one by one, as every such step did before."""

    __slots__ = ("cpu_ns", "wall_ns", "sends_lead", "_take", "_egress")

    def __init__(self, cpu_ns: Callable[[], int] = time.process_time_ns,
                 wall_ns: Callable[[], int] = time.monotonic_ns):
        self.cpu_ns, self.wall_ns = cpu_ns, wall_ns
        self.sends_lead = False
        self._take = self._egress = (wall_ns(), cpu_ns())

    def took(self, carry: bool = False) -> None:
        """A take begins. With ``carry`` (the step before was paced) what
        the last take still owed is owed from this one too: the CPU spent
        while the pace waited, and after it up to this take, which that
        pace could not see. A credit (the wall ahead of the CPU) is never
        carried, nor anything past a step that was not paced."""
        wall, cpu = self.wall_ns(), self.cpu_ns()
        owed = 0
        if carry:
            wall0, cpu0 = self._take
            owed = max((cpu - cpu0) - (wall - wall0), 0)
        self._take = (wall, cpu - owed)

    def egress_began(self) -> None:
        self._egress = (self.wall_ns(), self.cpu_ns())

    def egress_ended(self, back_pressured: bool) -> None:
        """The step's egress is over: update :attr:`sends_lead`."""
        wall, cpu = self._egress
        self.sends_lead = not back_pressured and \
            self.cpu_ns() - cpu > wall - self._take[0]

    def owed_ns(self) -> int:
        """The CPU spent since the last take less the wall since it: how
        long the next take still waits (nothing at 0 or below)."""
        wall, cpu = self._take
        return (self.cpu_ns() - cpu) - (self.wall_ns() - wall)


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
