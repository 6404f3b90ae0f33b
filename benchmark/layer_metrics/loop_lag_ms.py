"""Mean lateness of the event loop's 0.25 s wake-up over the window: the
program's lag sampler (``proto/metrics.py:_loop_lag_sampler``, the one
behind ``/healthz``) sums every sample's lag and counts the samples, and
``describe()`` says both: Δ``loop_lag_us`` / Δ``loop_lag_samples``,
between the window's ``start`` and ``end`` marks. A loop that is blocked,
not idle, reads high; a stall of seconds shows as seconds over some
eighty samples. Nothing where the routing process serves no metrics
endpoint, so that no sampler runs (``mesh_inprocess``), or on an older
commit."""

from benchmark import window_counters

LAYER = "host_path"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "broker_cpu_us_per_delivery"


def read(run):
    return window_counters.ratio(run, "loop_lag_us", "loop_lag_samples",
                                 scale=1e-3)
