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

# How long a launcher keeps asking a busy broker for its counters before
# it gives up (one request of a saturated event loop can take many
# seconds); the parent waits a little longer for the launcher's answer.
TOPOLOGY_WAIT_S = 60.0


class NoAnswer(Exception):
    """The deployment did not answer within the limit."""


def wait_for(ask: Callable[[float], Optional[dict]], what: str,
             wait_s: float = TOPOLOGY_WAIT_S, pause_s: float = 0.1) -> dict:
    """``ask(seconds_left)`` again and again until it returns something,
    for ``wait_s`` seconds in all; then :class:`NoAnswer`, which
    :func:`answer` turns into an ``error`` event that says so."""
    deadline = time.monotonic() + wait_s
    while True:
        found = ask(max(deadline - time.monotonic(), 0.05))
        if found is not None:
            return found
        if time.monotonic() + pause_s >= deadline:
            raise NoAnswer(f"{what}: no answer within {wait_s:.0f} s")
        time.sleep(pause_s)


def scalars(described: dict) -> dict:
    """What a launcher's ``counters`` passes through of the program's own
    description (``/debug/topology``'s ``device_plane``, a shard plane's
    ``describe()``): every key whose value is a number, a bool or None.
    A reader asks for a key with ``.get``: an older commit lacks it."""
    return {k: v for k, v in described.items()
            if v is None or isinstance(v, (bool, int, float))}


def emit(event: str, **fields) -> None:
    print(json.dumps({"event": event, **fields}), flush=True)


def serve(handlers: Dict[str, Callable[[dict], Optional[dict]]]) -> None:
    """Answer the parent's commands from a daemon thread, one at a time.
    A handler returns the event to send back, or None."""

    def loop() -> None:
        for line in sys.stdin:
            reply = answer(handlers, line)
            if reply is not None:
                print(json.dumps(reply), flush=True)

    threading.Thread(target=loop, name="bench-control", daemon=True).start()


def answer(handlers: Dict[str, Callable[[dict], Optional[dict]]],
           line: str) -> Optional[dict]:
    """One command's reply; a handler that raises gives an ``error``
    event, because the parent must hear about it."""
    try:
        cmd = json.loads(line)
        return handlers[cmd["cmd"]](cmd)
    except Exception as exc:
        return {"event": "error", "what": repr(exc)}


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
