"""From the launcher's spawn to the device plane's warm-up done (jax import,
backend start, compile or cache load of the warm-up programs)."""

LAYER = "process_start"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    return run.setup.get("broker_ready_s")
