"""Device time of one routing step: the time operations ran on the device
inside the step program's executions, over their number, in the traced
span."""

LAYER = "routing_step"
UNIT = "us"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "delivery_p50_ms"


def read(run):
    t = run.window.trace
    if not t or not t["steps"]:
        return None
    return t["step_device_s"] / t["steps"] * 1e6
