"""How late the open-loop publishers sent: p99 of (actual send - due) over
the frames due in the window. A late generator is not a fast broker."""

LAYER = "load_generator"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "delivery_p50_ms"


def read(run):
    late = run.window.late.percentile(99)
    return None if late is None else late / 1e6
