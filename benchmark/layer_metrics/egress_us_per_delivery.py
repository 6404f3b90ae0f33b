"""Synchronous host time of the device path's egress per delivery: the
summed durations of ``plane.encode`` (worker) and ``plane.egress`` (event
loop) over the deliveries ``plane.egress`` handed off, in the traced span.
Since PR 26 ``plane.egress`` holds the sends of every stream the pump
writes itself (on an idle link; a back-pressured step's in one native
batch since PR 31), not only the hand-off to the writers' queues."""

from benchmark import span_reduce

LAYER = "egress"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "broker_cpu_us_per_delivery"


def read(run):
    return span_reduce.us_per(run, "egress", "deliveries")
