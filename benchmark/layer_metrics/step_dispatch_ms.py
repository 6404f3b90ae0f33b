"""Time inside the jitted step's call: the ``plane.dispatch`` span of one
routing step, median over the steps in the traced span."""

from benchmark import span_reduce

LAYER = "routing_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "delivery_p50_ms"


def read(run):
    return span_reduce.step_median_ms(run, "dispatch")
