#!/usr/bin/env python3
"""What the event loop runs while a step's continuation waits: the two
thread hops of every complete step in a profiler trace, and the time the
loop thread spent inside each of the program's spans meanwhile.

A step leaves the loop after ``plane.take`` and comes back at
``plane.egress``; between them the worker thread runs ``plane.h2d`` …
``plane.encode``. The hops are what is left: ``plane.take``'s end to the
first worker span's start (the worker has to be woken and needs the
interpreter), and the last worker span's end to ``plane.egress``'s start
(the loop has to reach the pump's continuation behind whatever callbacks
stand before it). ``span_reduce``'s ``handoff`` is their sum, a median a
step; this says what filled them. The loop thread is the one
``plane.take`` runs on. Spans are flat, so the times by name add up, and
what no span covers is ``unnamed_ms``: the selector, callbacks of tasks
without a span (transport ingress, the writers' ``drain()``), or the loop
waiting for the interpreter.

Run as a child of the benchmark's parent, like ``span_reduce.py`` (whose
``load`` reads the file and whose ``SPAN_NAME`` tells the program's
spans):

    python benchmark/hop_reduce.py TRACE.xplane.pb

prints one JSON object (or ``null``): ``steps`` (complete steps with
worker spans), ``hop_ms`` (both hops, summed over them), ``hop1_ms`` and
``hop2_ms`` (the two apart), ``by_span_ms`` (loop-thread time inside each
span name during the hops) and ``unnamed_ms``; all totals over the trace,
so a ratio of two is a share and one over ``steps`` a mean a step. The
parent's side is :func:`hops_of`.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.span_reduce import (  # noqa: E402
    WORKER,
    Span,
    load,
    overlap,
    union,
)


def hops(spans: List[Span]) -> List[Tuple[Tuple[float, float],
                                          Tuple[float, float]]]:
    """``(hop 1, hop 2)`` as (start, end) in ns for every step the trace
    holds whole: one ``plane.take``, one ``plane.egress`` and worker
    spans between them. Steps are joined as ``span_reduce.steps_of``
    joins them: by the ``step`` stat, a span without one to the latest
    ``plane.take`` before it."""
    groups: Dict[object, List[Span]] = {}
    key: object = None
    for s in sorted((s for s in spans if s.name.startswith("plane.")),
                    key=lambda s: s.start):
        if "step" in s.stats:
            key = s.stats["step"]
        elif s.name == "plane.take":
            key = ("at", s.start)
        groups.setdefault(key, []).append(s)
    found = []
    for group in groups.values():
        take = [s for s in group if s.name == "plane.take"]
        egress = [s for s in group if s.name == "plane.egress"]
        work = [s for s in group if s.name in WORKER]
        if len(take) != 1 or len(egress) != 1 or not work:
            continue
        found.append(((take[0].end, min(s.start for s in work)),
                      (max(s.end for s in work), egress[0].start)))
    return found


def reduce(spans: List[Span]) -> Optional[dict]:
    """The numbers, or None when the trace holds no complete step."""
    steps = hops(spans)
    if not steps:
        return None
    loop_thread = next(s.thread for s in spans if s.name == "plane.take")
    first = union(hop for hop, _ in steps)
    second = union(hop for _, hop in steps)
    both = union(first + second)
    by_span = {}
    for name in sorted({s.name for s in spans if s.thread == loop_thread}):
        inside = overlap(both, union(
            (s.start, s.end) for s in spans
            if s.name == name and s.thread == loop_thread))
        if inside:
            by_span[name] = inside / 1e6
    hop_ms = sum(end - start for start, end in both) / 1e6
    return {"steps": len(steps), "hop_ms": hop_ms,
            "hop1_ms": sum(end - start for start, end in first) / 1e6,
            "hop2_ms": sum(end - start for start, end in second) / 1e6,
            "by_span_ms": by_span,
            "unnamed_ms": hop_ms - sum(by_span.values())}


def hops_of(run) -> Optional[dict]:
    """The reduced hops of a traced run, worked out once in a child (held
    to the CPU: it reads a file) and kept on ``run.window``, as
    ``span_reduce.spans_of`` keeps the spans."""
    w = run.window
    if hasattr(w, "hops"):
        return w.hops
    w.hops = None
    path = (getattr(w, "traced", None) or {}).get("file")
    if path:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), path],
            capture_output=True, text=True, timeout=300, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        if proc.returncode == 0:
            w.hops = json.loads(proc.stdout.strip().splitlines()[-1])
        else:
            print(f"[bench] hop reduction failed: {proc.stderr[-2000:]}",
                  flush=True)
    print(f"[bench] hops: {json.dumps(w.hops)}", flush=True)
    return w.hops


def main() -> int:
    print(json.dumps(reduce(load(sys.argv[1]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
