"""Of the takes of a step's egress buffer over the window, the share that
found no pooled buffer that fits and allocated a fresh one of 1 MB or
more (its page faults: ``native._egress_take``): Δ``egress_pool_fresh`` /
Δ``egress_pool_takes`` between the window's ``start`` and ``end`` marks.
A buffer stays out of the pool of three while any writer holds a stream
of it, so streams queued for the writers pin it. Nothing where the
commit has no such counter or no step took a buffer."""

from benchmark import window_counters

LAYER = "egress"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "broker_cpu_us_per_delivery"


def read(run):
    return window_counters.ratio(run, "egress_pool_fresh",
                                 "egress_pool_takes")
