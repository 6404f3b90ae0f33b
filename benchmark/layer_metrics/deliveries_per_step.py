"""Deliveries the device plane routed per step, warm-up and window together."""

LAYER = "egress"
UNIT = "deliveries"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "delivered_per_s"


def read(run):
    c = run.window.counters
    steps = c["final"]["steps"] - c["before"]["steps"]
    if not steps:
        return None
    return (c["final"]["messages_routed"]
            - c["before"]["messages_routed"]) / steps
