"""Of the pump's egress state over the window, the share spent in its own
writes on encrypting streams: Δ``egress_tls_write_us`` /
Δ``pump_egress_us`` between the window's ``start`` and ``end`` marks.
What is left of the state is the walk over the step's users, the
connection's checks and accounting, and every plain or queued hand-off.

It is what a change that lets a step's sends to TLS users leave the loop
(kernel TLS on the user's socket, or encryption in the worker phase) has
to bring down, with ``egress_batched_share`` going up from 0. Nothing
where the program does not say (an older commit) or the pump wrote no
encrypted stream in the window (plain TCP users)."""

from benchmark import window_counters

LAYER = "egress"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "delivered_per_s"


def read(run):
    delta = window_counters.moved(run, "egress_tls_write_us",
                                  "egress_tls_inline", "pump_egress_us")
    if delta is None or not delta["egress_tls_inline"] \
            or not delta["pump_egress_us"]:
        return None
    return delta["egress_tls_write_us"] / delta["pump_egress_us"]
