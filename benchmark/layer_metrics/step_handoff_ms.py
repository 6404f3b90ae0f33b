"""The step's two thread hops: from the end of ``plane.take`` on the event
loop to the first worker span's start, plus from the last worker span's
end to the start of ``plane.egress`` back on the loop; median over the
steps in the traced span."""

from benchmark import span_reduce

LAYER = "routing_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "delivery_p50_ms"


def read(run):
    return span_reduce.step_median_ms(run, "handoff")
