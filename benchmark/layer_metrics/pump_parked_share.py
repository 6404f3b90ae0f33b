"""Of the pump's wall time over the window, the share it spent parked:
awaiting its kick with nothing staged. The program's state account
(``pushcdn_tpu/broker/pump_common.py:PumpAccount``; six cumulative
microsecond counters in ``describe()`` that partition the one sequential
pump task's time): Δ``pump_parked_us`` over the sum of the six Δ, between
the window's ``start`` and ``end`` marks.

Near 0 in a closed loop at capacity, where the next batch is staged
before the step's egress ends. A stall of seconds with nothing staged
(PERF.md section 7) shows here as a tenth of a 20 s window; the other five
shares say which state grew when it does not. Nothing where the program
keeps no such account (an older commit)."""

from benchmark import window_counters

LAYER = "routing_step"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "delivered_per_s"
STATES = ("parked", "gate", "drain", "take", "worker", "egress")


def read(run):
    delta = window_counters.moved(
        run, *(f"pump_{state}_us" for state in STATES))
    if delta is None or not sum(delta.values()):
        return None
    return delta["pump_parked_us"] / sum(delta.values())
