"""Of the frames the planes staged over the window, the share that met a
full ring first: Δ``stage_full_frames`` (frames a ``stage_batch`` held
back, each then staged alone by the receive loop's retry on a 2 ms poll)
over Δ``frames_staged``, between the window's ``start`` and ``end`` marks.
A closed loop that fills the ring reads high by construction; what it
costs is the retry's per-frame path and its poll. Nothing where the
program does not count it (an older commit) or staged nothing."""

from benchmark import window_counters

LAYER = "stage_pack"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "broker_cpu_us_per_delivery"


def read(run):
    return window_counters.ratio(run, "stage_full_frames", "frames_staged")
