"""p99 publish-to-delivery time in a saturated cell. Recorded, not judged:
just above capacity it is the flow-control window over the rate."""

LAYER = "end_to_end"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "delivered_per_s"


def read(run):
    p99 = run.window.latency.percentile(99)
    return None if p99 is None else p99 / 1e6
