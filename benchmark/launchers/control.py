"""What every launcher shares: the line protocol with the benchmark's
parent, the profiler span and the device's memory peak.

A launcher is the process that owns the chip. The parent talks to it in
JSON lines: commands on stdin (``place``, ``counters``, ``trace``),
events on stdout (``ready``, ``placed``, ``counters``, ``traced``).
Only the chip's owner can trace it, so the profiler is driven from here:
a side thread traces the span and says where the file is. SIGTERM ends
a launcher; it stops what it started and exits 0.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import threading
import time
from typing import Callable, Dict, Optional


def emit(event: str, **fields) -> None:
    print(json.dumps({"event": event, **fields}), flush=True)


def serve(handlers: Dict[str, Callable[[dict], Optional[dict]]]) -> None:
    """Answer the parent's commands from a daemon thread, one at a time.
    A handler returns the event to send back, or None."""

    def loop() -> None:
        for line in sys.stdin:
            try:
                cmd = json.loads(line)
                reply = handlers[cmd["cmd"]](cmd)
            except Exception as exc:  # the parent must hear about it
                reply = {"event": "error", "what": repr(exc)}
            if reply is not None:
                print(json.dumps(reply), flush=True)

    threading.Thread(target=loop, name="bench-control", daemon=True).start()


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where the backend does
    not say, as the CPU)."""
    import jax
    peak = 0
    for device in jax.local_devices():
        stats = device.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def trace_span(cmd: dict) -> dict:
    """Trace ``cmd["seconds"]`` from now into ``cmd["dir"]``; answers once
    the profiler is on. Python-level tracing is off: it costs the event
    loop far more than the XLA runtime's own host events do."""
    import jax
    started = threading.Event()

    def run() -> None:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        t0 = time.monotonic_ns()
        jax.profiler.start_trace(cmd["dir"], profiler_options=options)
        t1 = time.monotonic_ns()
        started.set()
        time.sleep(cmd["seconds"])
        t2 = time.monotonic_ns()
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(cmd["dir"], "plugins", "profile",
                                       "*", "*.xplane.pb"))
        emit("traced", file=max(files, key=os.path.getmtime) if files else None,
             start_call_s=(t1 - t0) / 1e9, window_s=(t2 - t1) / 1e9,
             stop_call_s=(time.monotonic_ns() - t2) / 1e9)

    threading.Thread(target=run, name="bench-trace", daemon=True).start()
    started.wait(60)
    return {"event": "trace_armed"}
