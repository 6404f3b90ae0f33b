"""The part of the collective time during which no other operation ran on
the same device."""

LAYER = "mesh_tick"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "delivered_per_s"


def read(run):
    t = run.window.trace
    if not t or not t["collective_s"]:
        return None
    return t["collective_exposed_s"] / t["collective_s"]
