"""Wall time of one routing step in a saturated cell, where it sets the
rate (ring slots over this): ``plane.take`` start to ``plane.egress``
start, median over the steps in the traced span. ``step_wall_ms`` under
another name because what it moves differs."""

from benchmark import span_reduce

LAYER = "routing_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "delivered_per_s"


def read(run):
    return span_reduce.step_median_ms(run, "wall")
