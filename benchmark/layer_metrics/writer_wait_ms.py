"""Mean wait of a writer-queue entry over the window: from its enqueue
to the writer task's dequeue, every flow class. The program observes each
dequeue in ``cdn_writer_queue_delay_seconds``
(``Connection._account_entry``) and says the family's count and sum in
``describe()``: Δ``writer_wait_us`` / Δ``writer_dequeues``, between the
window's ``start`` and ``end`` marks. A stream the pump wrote itself on
an idle link never queued and is not in it. Nothing where the program
does not say (an older commit) or no writer dequeued in the window."""

from benchmark import window_counters

LAYER = "egress"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "broker_cpu_us_per_delivery"


def read(run):
    return window_counters.ratio(run, "writer_wait_us", "writer_dequeues",
                                 scale=1e-3)
