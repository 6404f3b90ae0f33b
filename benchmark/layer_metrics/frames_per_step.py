"""Frames staged per device step, warm-up and window together."""

LAYER = "stage_pack"
UNIT = "frames"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "delivered_per_s"


def read(run):
    c = run.window.counters
    steps = c["final"]["steps"] - c["before"]["steps"]
    if not steps:
        return None
    return (c["final"]["frames_staged"] - c["before"]["frames_staged"]) / steps
