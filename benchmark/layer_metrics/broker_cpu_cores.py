"""CPU of the routing process(es) over wall time in the window: about 1.0
means one event loop is the ceiling."""

LAYER = "host_path"
UNIT = "cores"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "delivered_per_s"


def read(run):
    return sum(run.window.cpu["route"]) / run.window.cpu_wall_s
