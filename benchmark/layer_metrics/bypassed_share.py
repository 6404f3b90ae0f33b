"""Frames the program's idle bypass host-routed over frames the cell's
publishers sent: the rest of ``staged_share``, for a cell that is meant
to stay off the device (1 means no frame of the cell was staged)."""

LAYER = "stage_pack"
UNIT = "ratio"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "delivery_p50_ms"


def read(run):
    w = run.window
    if not w.frames_sent:
        return None
    return 1.0 - w.frames_staged / w.frames_sent
