"""Time of the collective operations in one mesh tick, per device, from
the trace."""

LAYER = "mesh_tick"
UNIT = "us"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "delivered_per_s"


def read(run):
    t = run.window.trace
    if not t or not t["steps"] or not t["collective_s"]:
        return None
    return t["collective_s"] / t["steps"] * 1e6
