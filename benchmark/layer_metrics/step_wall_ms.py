"""Wall time of one routing step on the host: from the start of the pump's
``plane.take`` span to the start of its ``plane.egress`` span, median over
the steps in the traced span."""

from benchmark import span_reduce

LAYER = "routing_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "delivery_p50_ms"


def read(run):
    return span_reduce.step_median_ms(run, "wall")
