"""Median due-to-delivery time over the window of an open-loop cell whose
median is recorded, not judged: ``delivery_p50_ms``'s number, under a
name of its own where runs of one commit scatter wider than half the
largest bound the contract allows (PERF.md section 2). ``MOVES`` names
the cell's judged metric: a step in flight costs the host a core, so a
shorter period shows there too, if less sharply."""

LAYER = "end_to_end"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "broker_cpu_us_per_delivery"


def read(run):
    p50 = run.window.latency.percentile(50)
    return None if p50 is None else p50 / 1e6
