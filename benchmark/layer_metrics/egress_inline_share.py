"""Of a window's per-user stream hand-offs, the share the pump wrote
itself on an idle link (the rest went to the user's writer task): the
program's counters ``egress_inline`` and ``egress_queued``
(``/debug/topology``, passed through by the launcher) between the
window's ``start`` and ``end`` marks. Nothing where the commit has no such
counter, or where no step handed anything off (the bypass control).

The interval is the whole window (20 s), not the traced span that
``egress_batched_share`` sums over (the warm-up and the window's first
3 s): the two are not fractions of one total. 1.0 is the expected
reading wherever a user's stream of a step stays under the 64 KiB flush
unit (every device cell but ``global-steady`` today); it falls when
streams get long or links busy, so a constant 1.0 is a sound reading."""

LAYER = "egress"
UNIT = "ratio"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "broker_cpu_us_per_delivery"


def read(run):
    marks = run.window.counters
    start, end = marks.get("start", {}), marks.get("end", {})
    delta = {}
    for key in ("egress_inline", "egress_queued"):
        if start.get(key) is None or end.get(key) is None:
            return None
        delta[key] = end[key] - start[key]
    handed = delta["egress_inline"] + delta["egress_queued"]
    return delta["egress_inline"] / handed if handed else None
