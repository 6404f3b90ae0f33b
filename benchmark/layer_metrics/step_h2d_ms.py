"""Host-to-device time of one routing step: the sum of its ``plane.h2d``
spans (state and lane batches to the device), median over the steps in
the traced span."""

from benchmark import span_reduce

LAYER = "routing_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "delivery_p50_ms"


def read(run):
    return span_reduce.step_median_ms(run, "h2d")
