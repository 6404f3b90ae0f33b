"""How long the oldest staged frame had waited in the rings when the pump
took them: ``plane.take``'s ``ring_wait_us``, median over the steps in the
traced span."""

from benchmark import span_reduce

LAYER = "stage_pack"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "delivery_p50_ms"


def read(run):
    return span_reduce.step_median_ms(run, "ring_wait")
