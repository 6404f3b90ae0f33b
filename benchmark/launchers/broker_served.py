#!/usr/bin/env python3
"""Launcher ``broker_served``: SQLite discovery + ``bin/marshal`` + one
``bin/broker`` with the configuration's flags, the way upstream's local
cluster starts them.

This process IS the broker: it runs ``pushcdn_tpu.bin.broker.main()``
unchanged, with the argv the configuration gives, on its main thread. A
side thread waits for the device plane's warm-up, starts the marshal as
a child, and then answers the parent (``control.py``): counters come
from the broker's own ``/debug/topology``, the device's memory peak and
the profiler span from JAX in this process, because only the chip's
owner can read or trace it. Traced and untraced runs therefore start the
same process; the profiler call is the only difference.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.launchers import control  # noqa: E402


def _topology(port: int, timeout: float = 10.0):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/topology",
                timeout=timeout) as r:
            return json.loads(r.read().decode())
    except (urllib.error.URLError, OSError, ValueError):
        return None


def counters(port: int, wait_s: float = control.TOPOLOGY_WAIT_S) -> dict:
    """The ``counters`` event: every number the program says of its device
    plane, under the program's own names, beside what the launcher adds.
    A saturated broker answers late: ask again until ``wait_s`` is up."""
    topo = control.wait_for(
        lambda left: _topology(port, min(10.0, left)),
        "the broker's /debug/topology", wait_s)
    plane = topo["device_plane"]
    return {**control.scalars(plane),
            "event": "counters", "t_ns": time.monotonic_ns(),
            "users": topo["num_users"],
            "unmirrored": plane["unmirrored_users"],
            "memory_peak_bytes": control.memory_peak_bytes()}


def _accepts(port: int) -> bool:
    try:
        socket.create_connection(("127.0.0.1", port), timeout=1).close()
        return True
    except OSError:
        return False


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--workdir", required=True)
    args = ap.parse_args()
    with open(args.config) as f:
        cfg = json.load(f)
    from pushcdn_tpu.bin.common import free_ports
    db = os.path.join(args.workdir, "discovery.sqlite")
    pub, priv, metrics, marshal_port = free_ports(4)
    children = []
    t_spawn = time.monotonic_ns()

    def bring_up() -> None:
        plane = None
        while plane is None or plane["warmup_s"] is None:
            time.sleep(0.1)
            topo = _topology(metrics)
            plane = topo["device_plane"] if topo else None
        plane_ready_ns = time.monotonic_ns()
        env = {**os.environ, "PYTHONPATH": REPO + (
            os.pathsep + os.environ["PYTHONPATH"]
            if os.environ.get("PYTHONPATH") else "")}
        with open(os.path.join(args.workdir, "marshal.log"), "ab") as log:
            children.append(subprocess.Popen(
                [sys.executable, "-m", "pushcdn_tpu.bin.marshal",
                 "--discovery-endpoint", db,
                 "--bind-endpoint", f"127.0.0.1:{marshal_port}",
                 *cfg["marshal_flags"]],
                env=env, stdout=log, stderr=subprocess.STDOUT))
        while not _accepts(marshal_port):
            time.sleep(0.05)
        control.serve({"counters": lambda _cmd: counters(metrics),
                       "place": lambda _cmd: {"event": "placed"},
                       "trace": control.trace_span})
        control.emit(
            "ready", marshal=f"127.0.0.1:{marshal_port}",
            route_pids=[os.getpid()], spawn_ns=t_spawn,
            plane_ready_ns=plane_ready_ns,
            device={"platform": plane["platform"],
                    "kind": plane["device_kind"],
                    "count": plane["device_count"]},
            plane={k: plane[k] for k in ("delivery_impl", "kernels")},
            compile_cache=plane.get("compile_cache"))

    threading.Thread(target=bring_up, name="bench-bring-up",
                     daemon=True).start()
    sys.argv = [
        "pushcdn-broker", "--discovery-endpoint", db,
        "--public-advertise-endpoint", f"127.0.0.1:{pub}",
        "--public-bind-endpoint", f"127.0.0.1:{pub}",
        "--private-advertise-endpoint", f"127.0.0.1:{priv}",
        "--private-bind-endpoint", f"127.0.0.1:{priv}",
        "--metrics-bind-endpoint", f"127.0.0.1:{metrics}",
        *cfg["broker_flags"]]
    from pushcdn_tpu.bin import broker
    try:
        broker.main()  # returns after SIGTERM's drain
    finally:
        for child in children:
            if child.poll() is None:
                child.send_signal(signal.SIGTERM)
            try:
                child.wait(timeout=30)
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait()
    return 0


if __name__ == "__main__":
    sys.exit(main())
