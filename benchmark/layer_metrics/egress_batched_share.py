"""Of the traced span's per-user stream hand-offs, the share that left in
one native batch call (a back-pressured ``DevicePlane`` step's sends, PR
31): ``plane.egress``'s ``batched`` over its ``inline`` + ``queued``,
summed by ``span_reduce``. Nothing where the span has no ``batched`` stat
(the mesh group's, an older commit's) or handed nothing off.

The interval is the traced span (the warm-up and the window's first 3 s),
not the whole window that ``egress_inline_share`` takes its counters
over. 0 is the expected, sound reading in a cell whose steps are not
back-pressured (an open loop under its knee) or whose users come over TLS
(``idle_fd`` is ``None`` there); ``higher`` is better only where steps
are back-pressured, and a 0 elsewhere says that the path stayed out of
the way, which is what it should do."""

from benchmark import span_reduce

LAYER = "egress"
UNIT = "ratio"
BETTER = "higher"
SOURCE = "program_span"
MOVES = "broker_cpu_us_per_delivery"


def read(run):
    batched = span_reduce.stat_sum(run, "plane.egress", "batched")
    if batched is None:
        return None
    handed = (span_reduce.stat_sum(run, "plane.egress", "inline") or 0) \
        + (span_reduce.stat_sum(run, "plane.egress", "queued") or 0)
    return batched / handed if handed else None
