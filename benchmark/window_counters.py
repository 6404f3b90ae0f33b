"""What the program's cumulative counters moved by over the measured
window: the difference of the launcher's ``counters`` event between two of
the window's marks (``run.window.counters``), for the readers under
``layer_metrics/`` that are a ratio of two such differences.

Every counter read here is a sum that only grows (events,
microseconds), so the difference is the interval's and, where a launcher
sums over several brokers, the deployment's. The marks are ``start`` and
``end``, the window itself (``start`` exists only in a traced run, the
only kind that reports per-layer metrics); ``egress_inline_share.py``
reads the same interval by hand.
"""

from typing import Dict, Optional


def moved(run, *keys: str, first: str = "start", last: str = "end"
          ) -> Optional[Dict[str, float]]:
    """``{key: last - first}`` for every key, or None where a mark or a
    key is missing or None at either end (an older commit, a process that
    does not keep that counter)."""
    marks = run.window.counters
    lo, hi = marks.get(first, {}), marks.get(last, {})
    if any(lo.get(key) is None or hi.get(key) is None for key in keys):
        return None
    return {key: hi[key] - lo[key] for key in keys}


def ratio(run, numerator: str, divisor: str, scale: float = 1.0
          ) -> Optional[float]:
    """Δ``numerator`` / Δ``divisor`` × ``scale`` over the window; None
    where either is missing or the divisor did not move."""
    delta = moved(run, numerator, divisor)
    if delta is None or not delta[divisor]:
        return None
    return scale * delta[numerator] / delta[divisor]


def step_hop_ms(run) -> Optional[float]:
    """The two thread hops a step over the window, in ms: the pump's wait
    for its worker less the step's wall on the worker thread, over the
    steps taken (``step_hop_ms`` and ``sat_step_hop_ms`` are this number
    under the two end-to-end metrics it moves)."""
    delta = moved(run, "pump_worker_us", "worker_busy_us", "steps")
    if delta is None or not delta["steps"]:
        return None
    return (delta["pump_worker_us"] - delta["worker_busy_us"]) \
        / delta["steps"] / 1e3
