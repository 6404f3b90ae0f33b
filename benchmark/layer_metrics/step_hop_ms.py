"""The step's two thread hops over the whole window, a step: the pump's
wait for its worker (Δ``pump_worker_us``: ``await asyncio.to_thread`` of
the step, from the loop's side) less the step's wall measured on the
worker thread (Δ``worker_busy_us``), over Δ``steps``, between the window's
``start`` and ``end`` marks. What is left is the time to wake the worker
and the time the finished step's continuation waited for the loop.

``step_handoff_ms`` reads the same two gaps off the traced span (the
warm-up and the window's first 3 s, a median of its steps); this one is a
mean over every step of the 20 s. Nothing where the program has no such
counters (an older commit) or took no step."""

from benchmark import window_counters

LAYER = "routing_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "delivery_p50_ms"


def read(run):
    return window_counters.step_hop_ms(run)
