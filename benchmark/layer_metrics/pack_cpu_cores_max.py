"""The busiest client process's CPU share over the window (user + system,
from /proc). At 0.9 or more the clients bound the delivered rate, not the
broker."""

LAYER = "load_generator"
UNIT = "cores"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "delivered_per_s"


def read(run):
    return max(run.window.cpu["packs"]) / run.window.cpu_wall_s
