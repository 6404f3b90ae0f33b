"""Of the two thread hops of the traced span's steps, the share the event
loop spent inside spans of the program (``benchmark/hop_reduce.py``:
1 − ``unnamed_ms`` / ``hop_ms``): receive loops scanning and staging,
writers writing, the profiler's tick, while a step's worker waited to be
woken or its continuation waited for the loop. Lower is better: the
continuation waits behind less of the loop's own work. What no span covers
(the selector, callbacks of tasks without a span, the wait for the
interpreter) is the rest; ``[bench] hops:`` names every part. Nothing
where the trace holds no complete step."""

from benchmark import hop_reduce

LAYER = "routing_step"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "delivered_per_s"


def read(run):
    hops = hop_reduce.hops_of(run)
    if not hops or not hops["hop_ms"]:
        return None
    return 1 - hops["unnamed_ms"] / hops["hop_ms"]
