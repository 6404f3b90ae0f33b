#!/usr/bin/env python3
"""From a profiler trace (``.xplane.pb``) to numbers: device busy time,
time per routing step, collective time and its exposed part, the
operations that took most time and the longest idle gaps.

Run as a child of the benchmark's parent (which never imports jax):

    python benchmark/trace_reduce.py TRACE.xplane.pb --step-module NAME ...

prints one JSON object. Reading the file needs ``jax.profiler.ProfileData``
and nothing else of JAX; no backend is initialised.

What a trace holds (read off a real one from the v5e, see PERF.md): one
plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one event
per executed operation (named by its whole HLO text) and ``XLA Modules``
one per executed program (``jit_<function>(<fingerprint>)``); and
``/host:CPU`` with one line per host thread, holding the runtime's own
events (PjRt execute, transfers, ``np.asarray``). All on one clock, in
nanoseconds. ``Async XLA Ops`` (the copies between memory spaces that
overlap the operations) are left out of the busy time. On the CPU backend (the dry run) there is no device plane:
the operations are the host events that carry an ``hlo_op`` stat.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
COLLECTIVE = re.compile(
    r"^(all[-_]gather|all[-_]reduce|all[-_]to[-_]all|collective[-_]permute|"
    r"reduce[-_]scatter|collective[-_]broadcast)")
TOP = 10


def load(path: str) -> dict:
    """``{"devices": {name: {"ops": [...], "modules": [...]}},
    "host": {thread: [...]}}`` with events as (name, start, duration)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    host: Dict[str, List[Event]] = {}
    cpu_ops: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] = [(e.name, e.start_ns, e.duration_ns)
                                  for e in line.events]
                elif line.name == "XLA Modules":
                    dev["modules"] = [(e.name, e.start_ns, e.duration_ns)
                                      for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                events = host.setdefault(line.name, [])
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        cpu_ops.append((e.name, e.start_ns, e.duration_ns))
                        events.append((f"{stats.get('hlo_module', '?')}",
                                       e.start_ns, e.duration_ns))
                    else:
                        events.append((e.name, e.start_ns, e.duration_ns))
    if not devices and cpu_ops:
        # the CPU backend: programs run on host threads. Modules are not
        # events there; the module of an op is its ``hlo_module`` stat,
        # kept as the host event's name above.
        modules = [ev for events in host.values() for ev in events
                   if ev[0].startswith("jit_")]
        devices["/host:CPU"] = {"ops": cpu_ops, "modules": modules}
    return {"devices": devices, "host": host}


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint ones, in order."""
    merged: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def covered(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(end - start for start, end in intervals)


def overlap(a: Sequence[Tuple[float, float]],
            b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two disjoint, ordered interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _spans(events: Iterable[Event]) -> List[Tuple[float, float]]:
    return [(s, s + d) for _n, s, d in events]


def _base(name: str) -> str:
    """An operation's own name, without the HLO text that follows it on
    the TPU (``%fusion.12 = pred[...] fusion(...)``) and without its
    instance number (``fusion.12`` and ``fusion.7`` are both ``fusion``),
    so the top list groups by kind."""
    return re.sub(r"[.:]\d+$", "", name.split(" = ", 1)[0].lstrip("%"))


def reduce(trace: dict, step_modules: Sequence[str] = (),
           kernels: Sequence[str] = ()) -> dict:
    """The numbers. Times in seconds; device figures are averaged over the
    devices in the trace. ``step_modules`` are prefixes of the program
    names that are routing steps; ``kernels`` are substrings of operation
    names whose own time is wanted, with the HLO text of each distinct
    call (on the TPU it holds the operands' and the result's shapes, which
    a roofline reader takes from here and from nowhere else)."""
    devices = trace["devices"]
    n = len(devices)
    out = {"devices": n, "busy_s": 0.0, "steps": 0, "step_device_s": 0.0,
           "collective_s": 0.0, "collective_exposed_s": 0.0,
           "device_ops": [], "idle_gaps": [], "kernels": {}}
    if not n:
        return out
    op_time: Dict[str, float] = {}
    kernel_rows = {k: {"count": 0, "seconds": 0.0, "calls": {}}
                   for k in kernels}
    gaps: List[Tuple[float, float]] = []
    for index, (_name, dev) in enumerate(sorted(devices.items())):
        ops = dev["ops"]
        busy = union(_spans(ops))
        out["busy_s"] += covered(busy) / 1e9 / n
        steps = [m for m in dev["modules"]
                 if any(m[0].startswith(p) for p in step_modules)]
        if index == 0:
            out["steps"] = len(steps)
            gaps = [(b[1], a[0]) for b, a in zip(busy, busy[1:])]
        out["step_device_s"] += overlap(union(_spans(steps)), busy) / 1e9 / n
        coll = [e for e in ops if COLLECTIVE.match(_base(e[0]))]
        rest = union(_spans(e for e in ops
                            if not COLLECTIVE.match(_base(e[0]))))
        coll_u = union(_spans(coll))
        out["collective_s"] += covered(coll_u) / 1e9 / n
        out["collective_exposed_s"] += \
            (covered(coll_u) - overlap(coll_u, rest)) / 1e9 / n
        for name, _s, dur in ops:
            base = _base(name)
            op_time[base] = op_time.get(base, 0.0) + dur / 1e9 / n
            for k, row in kernel_rows.items():
                if k in base:
                    row["count"] += 1
                    row["seconds"] += dur / 1e9
                    call = row["calls"].setdefault(name, [0, 0.0])
                    call[0] += 1
                    call[1] += dur / 1e9
    out["kernels"] = kernel_rows
    out["device_ops"] = [[name, seconds] for name, seconds in sorted(
        op_time.items(), key=lambda kv: -kv[1])[:TOP]]
    out["idle_gaps"] = _label_gaps(gaps, trace["host"])
    return out


def _label_gaps(gaps: List[Tuple[float, float]],
                host: Dict[str, List[Event]]) -> List[list]:
    """The longest idle gaps of the first device, each named after the
    traced host event that covers most of it. The program carries no
    spans of its own yet, and Python is not traced, so a gap in which
    only the interpreter ran has no event to be named after."""
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    events = sorted((s, s + d, f"{thread.split('/')[0]}: {name}")
                    for thread, evs in host.items() for name, s, d in evs)
    rows = []
    for lo, hi in longest:
        best, best_cover = "no traced host event (Python between steps)", 0.0
        for s, e, label in events:
            if s >= hi:
                break
            cover = min(e, hi) - max(s, lo)
            if cover > best_cover:
                best, best_cover = label, cover
        rows.append([best, (hi - lo) / 1e9])
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--step-module", action="append", default=[])
    ap.add_argument("--kernel", action="append", default=[])
    ap.add_argument("--dump", action="store_true",
                    help="print planes, lines and the commonest event "
                         "names instead (for reading a trace by hand)")
    args = ap.parse_args()
    if args.dump:
        from jax.profiler import ProfileData
        for plane in ProfileData.from_file(args.trace).planes:
            print("PLANE", plane.name)
            for line in plane.lines:
                names: Dict[str, list] = {}
                for e in line.events:
                    row = names.setdefault(_base(e.name), [0, 0.0])
                    row[0] += 1
                    row[1] += e.duration_ns
                print("  LINE", line.name, sum(r[0] for r in names.values()))
                for name, (count, ns) in sorted(
                        names.items(), key=lambda kv: -kv[1][1])[:12]:
                    print(f"      {count:7d} x {ns / 1e6:10.3f} ms  {name}")
        return 0
    print(json.dumps(reduce(load(args.trace), args.step_module, args.kernel)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
