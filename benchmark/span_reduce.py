#!/usr/bin/env python3
"""From the program's own spans in a profiler trace to the host side of
the step: per span its count, total and median; per step the wall time
and what it is made of; per frame and per delivery the synchronous host
time of scalar ingress and of egress.

The spans are ``jax.profiler.TraceAnnotation`` events the program emits
(``pushcdn_tpu/parallel/spans.py`` names them): on ``/host:CPU``, one
line per thread, the name bare and the keyword arguments as the event's
stats. A span is told from the runtime's own host events by its name
alone, dotted and lower-case (``SPAN_NAME``: ``ingress.scan``,
``plane.take``, and whatever a later PR adds under such a name); no list
here has to know it. Lines of different threads may share a name, so
lines are told apart by their position. A program without spans (an older commit)
gives ``None`` here, and every reader then leaves its metric out.

Run as a child of the benchmark's parent (which never imports jax), like
``trace_reduce.py``:

    python benchmark/span_reduce.py TRACE.xplane.pb

prints one JSON object (or ``null``). The parent's side is
:func:`spans_of`, which the readers under ``layer_metrics/`` call.

The step join: ``plane.*`` spans that share a ``step`` stat are one step
(a span without the stat belongs to the latest ``plane.take`` before
it). A step counts when the trace holds both its ``plane.take`` and its
``plane.egress``. Per step, in ms:

- ``wall``: ``plane.take`` start to ``plane.egress`` start;
- ``handoff``: the two thread hops, ``plane.take`` end to the first
  worker span's start plus the last worker span's end to ``plane.egress``
  start;
- ``h2d``, ``dispatch``, ``d2h``, ``encode``: sums of that step's spans;
- ``ring_wait``: ``plane.take``'s ``ring_wait_us``.

What lies between two worker spans of a step (Python between the phases)
is in ``wall`` and in none of its parts.

Beside the join, for every span name: ``spans[name]`` (count, total and
median length) and ``stats[name][stat]``, the sum of each numeric stat
over that span's events in the trace (``step`` left out: it is an
index). A reader of a new span or stat asks
``spans_of(run)["stats"].get(name, {}).get(stat)`` and returns ``None``
where a commit's span has no such stat.
"""

from __future__ import annotations

import json
import os
import re
import statistics
import subprocess
import sys
from typing import Dict, List, NamedTuple, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.trace_reduce import covered, overlap, union  # noqa: E402

WORKER = ("plane.h2d", "plane.dispatch", "plane.d2h", "plane.encode")
LOOP = ("ingress.scan", "ingress.stage", "plane.take", "plane.egress")
PARTS = ("wall", "handoff", "h2d", "dispatch", "d2h", "encode", "ring_wait")
SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")


class Span(NamedTuple):
    name: str
    thread: int   # position of the thread's line in the host plane
    start: float  # ns
    end: float    # ns
    stats: dict


def load(path: str) -> List[Span]:
    """The program's spans in a trace file, in no particular order."""
    from jax.profiler import ProfileData
    spans: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for thread, line in enumerate(plane.lines):
            for e in line.events:
                if SPAN_NAME.match(e.name):
                    spans.append(Span(e.name, thread, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      dict(e.stats)))
    return spans


def steps_of(spans: List[Span]) -> List[Dict[str, float]]:
    """One row of ``PARTS`` (ms) per complete step, in time order."""
    groups: Dict[object, List[Span]] = {}
    key: object = None
    for s in sorted((s for s in spans if s.name.startswith("plane.")),
                    key=lambda s: s.start):
        if "step" in s.stats:
            key = s.stats["step"]
        elif s.name == "plane.take":
            key = ("at", s.start)
        groups.setdefault(key, []).append(s)
    rows = []
    for group in groups.values():
        take = [s for s in group if s.name == "plane.take"]
        egress = [s for s in group if s.name == "plane.egress"]
        if len(take) != 1 or len(egress) != 1:
            continue  # the trace began or ended inside this step
        take, egress = take[0], egress[0]
        work = [s for s in group if s.name in WORKER]
        first = min((s.start for s in work), default=egress.start)
        last = max((s.end for s in work), default=take.end)
        row = {"wall": egress.start - take.start,
               "handoff": (first - take.end) + (egress.start - last),
               "ring_wait": take.stats.get("ring_wait_us", 0) * 1e3}
        for name in WORKER:
            row[name.split(".")[1]] = sum(
                s.end - s.start for s in work if s.name == name)
        rows.append({k: v / 1e6 for k, v in row.items()})
    return rows


def reduce(spans: List[Span]) -> Optional[dict]:
    """The numbers, or None when the trace holds none of the program's
    spans."""
    if not spans:
        return None
    out: dict = {"spans": {}, "stats": {}}
    known = LOOP + WORKER
    for name in known + tuple(sorted({s.name for s in spans} - set(known))):
        mine = [s for s in spans if s.name == name]
        if not mine:
            continue
        durs = [(s.end - s.start) / 1e6 for s in mine]
        out["spans"][name] = {"count": len(durs), "total_ms": sum(durs),
                              "median_ms": statistics.median(durs)}
        sums = out["stats"][name] = {}
        for s in mine:
            for stat, value in s.stats.items():
                if stat != "step" and isinstance(value, (int, float)) \
                        and not isinstance(value, bool):
                    sums[stat] = sums.get(stat, 0) + value
    rows = steps_of(spans)
    out["steps"] = len(rows)
    out["step_ms"] = {part: statistics.median(r[part] for r in rows)
                      for part in PARTS} if rows else {}

    def total(names, stat_of, stat):
        return {"us": sum((s.end - s.start) / 1e3 for s in spans
                          if s.name in names),
                stat: sum(s.stats.get(stat, 0) for s in spans
                          if s.name == stat_of)}
    out["ingress"] = total(("ingress.scan", "ingress.stage"),
                           "ingress.scan", "frames")
    out["egress"] = total(("plane.encode", "plane.egress"),
                          "plane.egress", "deliveries")
    # how much of each worker phase the event-loop thread spent inside
    # spans of its own (busy with ingress and egress, so holding the
    # interpreter) — read by PERF.md, not by a metric
    loop = union((s.start, s.end) for s in spans if s.name in LOOP)
    out["loop_busy_share_during"] = {}
    for name in WORKER:
        phase = union((s.start, s.end) for s in spans if s.name == name)
        if phase:
            out["loop_busy_share_during"][name.split(".")[1]] = \
                overlap(phase, loop) / covered(phase)
    return out


# ---- the parent's side ----------------------------------------------------

def spans_of(run) -> Optional[dict]:
    """The reduced spans of a traced run, worked out once in a child
    (held to the CPU: it reads a file) and kept on ``run.window``."""
    w = run.window
    if hasattr(w, "spans"):
        return w.spans
    w.spans = None
    path = (getattr(w, "traced", None) or {}).get("file")
    if path:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), path],
            capture_output=True, text=True, timeout=300, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        if proc.returncode == 0:
            w.spans = json.loads(proc.stdout.strip().splitlines()[-1])
        else:
            print(f"[bench] span reduction failed: {proc.stderr[-2000:]}",
                  flush=True)
    print(f"[bench] spans: {json.dumps(w.spans)}", flush=True)
    return w.spans


def step_median_ms(run, part: str) -> Optional[float]:
    """Median over the traced steps of one of ``PARTS``."""
    spans = spans_of(run)
    return spans["step_ms"].get(part) if spans else None


def stat_sum(run, span: str, stat: str) -> Optional[float]:
    """Sum of one stat over one span's events in the traced window; None
    where the trace has no such span or the span no such stat."""
    spans = spans_of(run)
    return spans.get("stats", {}).get(span, {}).get(stat) if spans else None


def us_per(run, side: str, unit: str) -> Optional[float]:
    """Span time of ``ingress`` or ``egress`` over its count of units."""
    spans = spans_of(run)
    if not spans or not spans[side][unit]:
        return None
    return spans[side]["us"] / spans[side][unit]


def main() -> int:
    print(json.dumps(reduce(load(sys.argv[1]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
