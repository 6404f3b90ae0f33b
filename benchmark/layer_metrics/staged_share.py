"""Frames the device plane staged over frames the cell's publishers
sent, warm-up and window together (the program's counter against the
benchmark's own; the warm-up prelude's frames, always staged, are left
out of both). What the idle bypass host-routed is the rest."""

LAYER = "stage_pack"
UNIT = "ratio"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "broker_cpu_us_per_delivery"


def read(run):
    w = run.window
    if not w.frames_sent:
        return None
    return w.frames_staged / w.frames_sent
