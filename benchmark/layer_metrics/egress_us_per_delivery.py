"""Synchronous host time of the device path's egress per delivery: the
summed durations of ``plane.encode`` (worker) and ``plane.egress`` (event
loop) over the deliveries ``plane.egress`` handed to the writers, in the
traced span."""

from benchmark import span_reduce

LAYER = "egress"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "broker_cpu_us_per_delivery"


def read(run):
    return span_reduce.us_per(run, "egress", "deliveries")
