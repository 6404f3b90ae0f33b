"""Time reading the decisions back: the sum of one routing step's
``plane.d2h`` spans (the wait for the device included), median over the
steps in the traced span."""

from benchmark import span_reduce

LAYER = "routing_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "delivery_p50_ms"


def read(run):
    return span_reduce.step_median_ms(run, "d2h")
