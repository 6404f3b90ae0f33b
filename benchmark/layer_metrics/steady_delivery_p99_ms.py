"""p99 due-to-delivery time over the window of an open-loop cell.
Recorded, not judged: with 2 s of warm-up it is the tail of the backlog
the cell builds in its first seconds, not the steady tail, and it swings
from run to run with that backlog's size (PERF.md section 2)."""

LAYER = "end_to_end"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "delivery_p50_ms"


def read(run):
    p99 = run.window.latency.percentile(99)
    return None if p99 is None else p99 / 1e6
