"""Synchronous host time of a broker link's ingress per received frame:
the summed durations of ``links.scan`` and ``links.stage``
(``broker_receive_loop``'s scan of a receive batch and its one
``stage_batch`` call) over the frames the scans saw, in the traced span
of the traced broker. Both spans are compute on the event loop with no
await inside, so this is CPU: ``ingress_us_per_frame``'s twin for the
frames a peer sent. Nothing where the trace has no such span (a broker
without a peer, an older commit)."""

from benchmark import span_reduce

LAYER = "broker_links"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "broker_cpu_us_per_delivery"


def read(run):
    frames = span_reduce.stat_sum(run, "links.scan", "frames")
    if not frames:
        return None
    spans = span_reduce.spans_of(run)["spans"]
    return 1e3 * sum(spans[name]["total_ms"]
                     for name in ("links.scan", "links.stage")
                     if name in spans) / frames
