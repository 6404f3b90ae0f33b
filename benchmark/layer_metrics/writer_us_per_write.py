"""Synchronous loop time of one writer's write over the window: the
program times every ``AsyncioStream.write`` / ``writev`` of a writer task
(the transport's ``write`` or ``writelines`` alone, the ``send()``
included where the buffer was empty; never the ``drain()``, never the
pump's own inline write, which lies inside ``plane.egress``) and says
their number and summed time in ``describe()``: Δ``writer_write_us`` /
Δ``writer_writes``, between the window's ``start`` and ``end`` marks.
Nothing where the program does not say (an older commit) or no writer
task wrote in the window."""

from benchmark import window_counters

LAYER = "egress"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "broker_cpu_us_per_delivery"


def read(run):
    return window_counters.ratio(run, "writer_write_us", "writer_writes")
