"""The step's two thread hops over the whole window in a saturated cell,
where the step's length sets the rate: ``step_hop_ms`` under another name
because what it moves differs (as ``sat_step_wall_ms`` is
``step_wall_ms``). (Δ``pump_worker_us`` − Δ``worker_busy_us``) / Δ``steps``
between the window's ``start`` and ``end`` marks; summed over the brokers
where the launcher sums, so a mean over every broker's steps."""

from benchmark import window_counters

LAYER = "routing_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "delivered_per_s"


def read(run):
    return window_counters.step_hop_ms(run)
