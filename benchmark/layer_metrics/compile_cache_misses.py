"""Persistent-cache misses of the chip's owner by the end of the run: 0 on
every run of a cell in a checkout after its first."""

LAYER = "compile_cache"
UNIT = "programs"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    return run.window.counters["final"]["cache_misses"]
