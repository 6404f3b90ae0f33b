"""1 - (time an operation ran on the device) / (traced span), averaged
over the chips used."""

LAYER = "device"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "broker_cpu_us_per_delivery"


def read(run):
    t = run.window.trace
    if not t or not t["window_s"]:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
