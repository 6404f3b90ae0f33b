"""Duplicate deliveries over unique ones, as the subscribers counted them
(legal for at-least-once delivery; each costs the broker a send)."""

LAYER = "client_decode"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "broker_cpu_us_per_delivery"


def read(run):
    if not run.window.unique:
        return None
    return run.window.duplicates / run.window.unique
