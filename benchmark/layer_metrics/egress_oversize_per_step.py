"""Of a step's per-user stream hand-offs, how many were longer than one
flush unit (``Connection._BATCH_COALESCE_LIMIT``, 64 KiB): streams no
idle link takes from the pump, in the native batch or one by one, so
they go to the writer tasks and the user's later streams queue behind
them. Δ``egress_oversize`` / Δ``steps`` between the window's ``start``
and ``end`` marks (the program's counters, ``senders.egress_streams``).
Nothing where the commit has no such counter or no step ran.

A skewed topic draw (``zipf-sat``) sends the hottest topic's four
subscribers over the unit on nearly every step, so it reads about 4
there; a uniform draw over 250 topics reads 0. Lower is better: each
such stream leaves the batch for the loop's writers."""

from benchmark import window_counters

LAYER = "egress"
UNIT = "streams"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "delivered_per_s"


def read(run):
    return window_counters.ratio(run, "egress_oversize", "steps")
