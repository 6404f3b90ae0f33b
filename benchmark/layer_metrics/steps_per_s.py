"""Device steps per second of the window (counters read at its two ends)."""

LAYER = "routing_step"
UNIT = "steps/s"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "delivery_p50_ms"


def read(run):
    c = run.window.counters
    if "start" not in c:
        return None
    return (c["end"]["steps"] - c["start"]["steps"]) \
        / ((c["end"]["t_ns"] - c["start"]["t_ns"]) / 1e9)
