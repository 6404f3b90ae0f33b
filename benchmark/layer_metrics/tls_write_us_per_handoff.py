"""Loop time of one encrypted hand-off the pump wrote itself, over the
window: the program times every ``write_nowait`` of ``egress_streams`` on
a stream that encrypts above its socket (users on TCP+TLS: the ``bytes()``
copy of the step's pooled buffer, Python's ``ssl`` record layer and the
transport's ``send()``, all on the event loop) and says their number and
summed time in ``describe()``: Δ``egress_tls_write_us`` /
Δ``egress_tls_inline``, between the window's ``start`` and ``end`` marks.
A hand-off that went to its writer task is in neither
(``writer_us_per_write`` has its write). Nothing where the program does
not say (an older commit) or the pump wrote no encrypted stream in the
window (plain TCP users)."""

from benchmark import window_counters

LAYER = "egress"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "broker_cpu_us_per_delivery"


def read(run):
    return window_counters.ratio(run, "egress_tls_write_us",
                                 "egress_tls_inline")
