"""Synchronous host time of scalar ingress per received frame: the summed
durations of ``ingress.scan`` and ``ingress.stage`` over the frames the
scans saw, in the traced span. Both spans are compute on the event loop
with no await inside, so this is CPU."""

from benchmark import span_reduce

LAYER = "scalar_ingress"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "broker_cpu_us_per_delivery"


def read(run):
    return span_reduce.us_per(run, "ingress", "frames")
