"""Synchronous host time of the broker↔broker leg at a frame's origin per
(frame, peer) send: the summed durations of ``links.forward`` (the user
loop's pass over a staged batch: the interest query, a clone and an
append for each interested peer, and the host route of what the device
left) over the sends it appended for peers (``forwards``), in the traced
span of the traced broker. The write itself is the link's writer task's
and is not in it. Nothing where the trace has no such span (a broker
without a peer, an older commit) or nothing was forwarded."""

from benchmark import span_reduce

LAYER = "broker_links"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "broker_cpu_us_per_delivery"


def read(run):
    forwards = span_reduce.stat_sum(run, "links.forward", "forwards")
    if not forwards:
        return None
    spans = span_reduce.spans_of(run)["spans"]
    return 1e3 * spans["links.forward"]["total_ms"] / forwards
