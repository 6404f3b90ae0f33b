#!/usr/bin/env python3
"""Launcher ``hostlinks_served``: SQLite discovery + ``bin/marshal`` +
several ``bin/broker`` processes with the configuration's flags, one chip
each, meshed over their private TCP endpoints by the program's own
heartbeat: upstream's local cluster with more than one broker.

This process IS one of the brokers, the one the configuration names
(``traced_broker``): it runs ``pushcdn_tpu.bin.broker.main()`` unchanged
on its main thread, as ``broker_served`` runs its one, so the profiler
span and the program's spans are that broker's. The others are
``python -m pushcdn_tpu.bin.broker`` children, all started at once. Each
process is held to its chip by ``CHIP_RECIPE`` with
``TPU_VISIBLE_DEVICES=<i>`` in its environment before it imports jax; a
process cannot tell which chip it holds, so ``i`` here is the chip's only
name. On an explicit ``JAX_PLATFORMS=cpu`` (the dry run) none of it is set.

A side thread waits for every plane's warm-up, then for the mesh (every
broker's ``/debug/topology`` shows all the others: the links are dialled
by the brokers' own heartbeat tasks, which run live), starts the marshal
and answers the parent (``control.py``):

- ``place`` steers group *k* onto broker *k* through discovery's load
  figure, which is connections plus outstanding permits: it writes
  permits nobody can redeem for the other brokers, which a heartbeat
  (it rewrites the connections alone) does not undo; and it checks, at
  the next command, that the group before landed where it should;
- the first ``counters`` after the last group waits until interest has
  crossed: every broker holds every peer's topics and every user's home;
- ``counters`` sums over the brokers what the program counts, ORs
  ``disabled`` and gives the fullest chip's memory peak.

A link, a placement or interest that is not as it should be within
``control.TOPOLOGY_WAIT_S`` is an ``error`` event: never a measured run.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sqlite3
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.launchers import control  # noqa: E402
from benchmark.launchers.broker_served import _accepts, _topology  # noqa: E402

# One chip a process on a host with several (PERF.md section 7, the probe
# of PR 33): libtpu skips its lock file only where the per-process bounds
# say the process takes less than the host; TPU_VISIBLE_CHIPS alone fails.
CHIP_RECIPE = {"TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
               "TPU_PROCESS_BOUNDS": "1,1,1"}
# what a sum over the brokers would misstate: the largest is reported
LARGEST = ("warmup_s", "device_memory_peak_bytes")
STEER_KEY = b"benchmark placement"  # the steering permits' public key


class Unsound(Exception):
    """The deployment is not the one the configuration describes."""


def chip_env(chip: int) -> Dict[str, str]:
    return {**CHIP_RECIPE, "TPU_VISIBLE_DEVICES": str(chip)}


def program_counts_its_links() -> bool:
    """Whether this checkout's device plane says ``link_frames_staged``:
    read off its source, so that a program from before the broker↔broker
    leg was counted is refused at once and not after a warm-up."""
    with open(os.path.join(REPO, "pushcdn_tpu", "broker",
                           "device_plane.py")) as f:
        return '"link_frames_staged"' in f.read()


def steer(db: str, busy: List[str], rows: int) -> None:
    """Make ``busy`` brokers look loaded to the marshal: ``rows`` permits
    each that no user holds (negative numbers; a real permit is 2 or
    more), in place of those of the call before. None at all once every
    group is placed."""
    handle = sqlite3.connect(db, timeout=30, isolation_level=None)
    try:
        handle.execute("BEGIN IMMEDIATE")
        handle.execute("DELETE FROM permits WHERE permit < 0")
        handle.executemany(
            "INSERT INTO permits (permit, broker, public_key, expiry) "
            "VALUES (?, ?, ?, ?)",
            [(-(1 + b * rows + r), ident, STEER_KEY, time.time() + 3600.0)
             for b, ident in enumerate(busy) for r in range(rows)])
        handle.execute("COMMIT")
    finally:
        handle.close()


def misplaced(group: int, before: List[int], after: List[int]
              ) -> Optional[str]:
    """What is wrong with where ``group`` landed, None when it is all on
    broker ``group``: every other broker holds who it held."""
    if after[group] > before[group] and all(
            a == b for i, (a, b) in enumerate(zip(after, before))
            if i != group):
        return None
    return (f"group {group} did not land on broker {group} alone: users "
            f"by broker {before} before it, {after} after")


def links_missing(topologies: List[dict]) -> Optional[str]:
    """None once every broker shows a link to each of the others."""
    want = len(topologies) - 1
    have = [len(t["peers"]) for t in topologies]
    if all(n == want for n in have):
        return None
    return (f"{sum(have) // 2} of {want * len(topologies) // 2} links: "
            f"peers by broker {have}")


def interest_missing(topologies: List[dict]) -> Optional[str]:
    """None once every broker holds, of each peer, as many topics as that
    peer's own users subscribe to, and a home for every user there is."""
    local = {t["identity"]: len(t["interest"]["topic_cardinality"])
             for t in topologies}
    users = sum(t["num_users"] for t in topologies)
    for t in topologies:
        for peer in t["peers"]:
            if peer["topics"] != local.get(peer["id"]):
                return (f"broker {t['identity']} holds {peer['topics']} "
                        f"topics of {peer['id']}, whose users have "
                        f"{local.get(peer['id'])}")
        if t["interest"]["direct_map_size"] != users:
            return (f"broker {t['identity']} knows the home of "
                    f"{t['interest']['direct_map_size']} of {users} users")
    return links_missing(topologies)


def summed(planes: List[dict]) -> dict:
    """One ``device_plane`` object for the deployment: what is a count
    summed, a bool OR-ed, ``LARGEST`` by their largest; a key some broker
    does not say is left out (an older program)."""
    out = {}
    for key in planes[0]:
        values = [p.get(key) for p in planes]
        if any(v is None for v in values):
            continue
        if all(isinstance(v, bool) for v in values):
            out[key] = any(values)
        elif all(isinstance(v, (int, float)) for v in values):
            out[key] = max(values) if key in LARGEST else sum(values)
    return out


class Deployment:
    """The brokers' metrics ports, and what the parent's commands need."""

    def __init__(self, cfg: dict, db: str, metrics: List[int],
                 idents: List[str]):
        self.cfg, self.db = cfg, db
        self.metrics, self.idents = metrics, idents
        self.pool = ThreadPoolExecutor(len(metrics))
        self.pending: Optional[tuple] = None  # (group, users before it)
        self.placed_ns: Optional[int] = None  # the last ``placed``
        self.mesh_formed_s: Optional[float] = None
        self.interest_synced_s: Optional[float] = None
        self.marshal_failed: List[str] = []  # what a failed start said

    def topologies(self, wait_s: float = control.TOPOLOGY_WAIT_S
                   ) -> List[dict]:
        """Every broker's ``/debug/topology``, asked side by side; a busy
        broker is asked again for ``wait_s`` in all."""
        return list(self.pool.map(
            lambda port: control.wait_for(
                lambda left: _topology(port, min(10.0, left)),
                f"/debug/topology on port {port}", wait_s), self.metrics))

    def settle(self, missing, what: str) -> List[dict]:
        """Ask until ``missing(topologies)`` finds nothing; what it found
        last is the error when the limit is up."""
        deadline = time.monotonic() + control.TOPOLOGY_WAIT_S
        while True:
            topologies = self.topologies()
            found = missing(topologies)
            if found is None:
                return topologies
            if time.monotonic() > deadline:
                raise Unsound(f"{what} after {control.TOPOLOGY_WAIT_S:.0f} "
                              f"s: {found}")
            time.sleep(0.1)

    def check_placement(self) -> None:
        if self.pending is None:
            return
        (group, before), self.pending = self.pending, None
        found = misplaced(
            group, before, [t["num_users"] for t in self.topologies()])
        if found is not None:
            raise Unsound(found)

    def place(self, cmd: dict) -> dict:
        self.check_placement()
        group = cmd["group"]
        if not 0 <= group < len(self.idents):
            raise Unsound(f"group {group} of {len(self.idents)} brokers")
        before = [t["num_users"] for t in self.topologies()]
        steer(self.db, [ident for i, ident in enumerate(self.idents)
                        if i != group], self.cfg["users"])
        self.pending = (group, before)
        self.placed_ns = time.monotonic_ns()
        self.interest_synced_s = None
        return {"event": "placed"}

    def counters(self, _cmd: dict) -> dict:
        if self.pending is not None:
            self.check_placement()
            steer(self.db, [], 0)
        if self.interest_synced_s is None and self.placed_ns is not None:
            topologies = self.settle(interest_missing,
                                     "interest has not crossed")
            self.interest_synced_s = \
                (time.monotonic_ns() - self.placed_ns) / 1e9
        else:
            topologies = self.topologies()
        planes = [t["device_plane"] for t in topologies]
        return {**summed(planes),
                "event": "counters", "t_ns": time.monotonic_ns(),
                "users": sum(t["num_users"] for t in topologies),
                "users_by_broker": [t["num_users"] for t in topologies],
                "unmirrored": sum(p["unmirrored_users"] for p in planes),
                "memory_peak_bytes": max(
                    p.get("device_memory_peak_bytes", 0) for p in planes),
                "mesh_formed_s": self.mesh_formed_s,
                "interest_synced_s": self.interest_synced_s,
                "marshal_failed": self.marshal_failed}


def _die_with_parent() -> None:
    """In a child, before it becomes the broker: SIGTERM when the thread
    that started it is gone, so that no broker keeps its chip after a
    launcher that was killed (prctl's PR_SET_PDEATHSIG)."""
    import ctypes
    ctypes.CDLL(None, use_errno=True).prctl(1, int(signal.SIGTERM))


def _exit_on_sigterm(*_signal) -> None:
    """Until the broker's own drain handler is in place: unwind through
    ``main``'s ``finally``, which stops the children."""
    raise SystemExit(0)


def broker_argv(cfg: dict, db: str, pub: int, priv: int, metrics: int
                ) -> List[str]:
    return ["--discovery-endpoint", db,
            "--public-advertise-endpoint", f"127.0.0.1:{pub}",
            "--public-bind-endpoint", f"127.0.0.1:{pub}",
            "--private-advertise-endpoint", f"127.0.0.1:{priv}",
            "--private-bind-endpoint", f"127.0.0.1:{priv}",
            "--metrics-bind-endpoint", f"127.0.0.1:{metrics}",
            *cfg["broker_flags"]]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--workdir", required=True)
    args = ap.parse_args()
    with open(args.config) as f:
        cfg = json.load(f)
    brokers, me = cfg["brokers"], cfg["traced_broker"]
    if cfg["placement_groups"] != brokers:
        print(f"hostlinks launcher: {cfg['placement_groups']} placement "
              f"groups for {brokers} brokers", file=sys.stderr)
        return 2
    if not program_counts_its_links():
        print("hostlinks launcher: this program's device plane does not "
              "count its broker links (no link_frames_staged): it is from "
              "before the deployment was supported", file=sys.stderr)
        return 2
    on_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    from pushcdn_tpu.bin.common import free_ports
    db = os.path.join(args.workdir, "discovery.sqlite")
    ports = free_ports(3 * brokers)
    pub, priv, metrics = (ports[0:brokers], ports[brokers:2 * brokers],
                          ports[2 * brokers:3 * brokers])
    deployment = Deployment(
        cfg, db, metrics,
        [f"127.0.0.1:{pub[i]}/127.0.0.1:{priv[i]}" for i in range(brokers)])
    env = {**os.environ, "PYTHONPATH": REPO + (
        os.pathsep + os.environ["PYTHONPATH"]
        if os.environ.get("PYTHONPATH") else "")}
    children: Dict[str, subprocess.Popen] = {}
    t_spawn = time.monotonic_ns()

    def spawn(name: str, module: str, argv: List[str], extra: dict,
              **popen) -> None:
        with open(os.path.join(args.workdir, f"{name}.log"), "ab") as log:
            children[name] = subprocess.Popen(
                [sys.executable, "-m", module, *argv], env={**env, **extra},
                stdout=log, stderr=subprocess.STDOUT, **popen)

    signal.signal(signal.SIGTERM, _exit_on_sigterm)
    for i in range(brokers):
        if i != me:
            # from the main thread, which lives as long as the process
            spawn(f"broker{i}", "pushcdn_tpu.bin.broker",
                  broker_argv(cfg, db, pub[i], priv[i], metrics[i]),
                  {} if on_cpu else chip_env(i),
                  preexec_fn=_die_with_parent)
    if not on_cpu:
        os.environ.update(chip_env(me))  # before anything imports jax

    def log_end(name: str) -> str:
        with open(os.path.join(args.workdir, f"{name}.log"),
                  errors="replace") as log:
            return log.read()[-1500:]

    def alive() -> None:
        """Every broker child still runs, else :class:`Unsound`."""
        for name, child in children.items():
            if name != "marshal" and child.poll() is not None:
                raise Unsound(f"{name} exited with code {child.returncode}; "
                              f"its log ends: {log_end(name)}")

    def start_marshal() -> int:
        """The marshal on a port picked just now (one picked with the
        brokers' would wait half a minute for its bind), started again,
        twice at most, if it exits before it accepts: once in PR 35's
        first 13 runs on the chip it exited with code 1 within 2 s and
        its log was lost with the machine. What a failed start said is
        kept for ``counters``, so that a run that needed a second start
        shows why."""
        for _attempt in range(3):
            port, = free_ports(1)
            spawn("marshal", "pushcdn_tpu.bin.marshal",
                  ["--discovery-endpoint", db,
                   "--bind-endpoint", f"127.0.0.1:{port}",
                   *cfg["marshal_flags"]], {})
            marshal = children["marshal"]
            while marshal.poll() is None:
                if _accepts(port):
                    return port
                time.sleep(0.05)
                alive()
            deployment.marshal_failed.append(
                f"code {marshal.returncode}: {log_end('marshal')}")
            print(f"hostlinks launcher: the marshal exited at its start: "
                  f"{deployment.marshal_failed[-1]}", file=sys.stderr)
        raise Unsound("the marshal exited at its start three times; the "
                      f"last: {deployment.marshal_failed[-1]}")

    def bring_up() -> None:
        planes: List[Optional[dict]] = [None] * brokers
        while not all(p and p["warmup_s"] is not None for p in planes):
            time.sleep(0.1)
            alive()
            for i, port in enumerate(metrics):
                topo = _topology(port, 2.0)
                planes[i] = topo["device_plane"] if topo else None
        plane_ready_ns = time.monotonic_ns()
        deployment.settle(lambda t: alive() or links_missing(t),
                          "the mesh has not formed")
        deployment.mesh_formed_s = \
            (time.monotonic_ns() - plane_ready_ns) / 1e9
        marshal_port = start_marshal()
        control.serve({"counters": deployment.counters,
                       "place": deployment.place,
                       "trace": control.trace_span})
        traced = planes[me]
        control.emit(
            "ready", marshal=f"127.0.0.1:{marshal_port}",
            route_pids=[os.getpid() if i == me
                        else children[f"broker{i}"].pid
                        for i in range(brokers)],
            spawn_ns=t_spawn, plane_ready_ns=plane_ready_ns,
            device={"platform": traced["platform"],
                    "kind": traced["device_kind"],
                    "count": sum(p["device_count"] for p in planes)},
            plane={k: traced[k] for k in ("delivery_impl", "kernels")},
            compile_cache=traced.get("compile_cache"))

    def guarded() -> None:
        try:
            bring_up()
        except Exception as exc:  # the parent must hear, then all stops
            control.emit("error", what=repr(exc))
            os.kill(os.getpid(), signal.SIGTERM)

    threading.Thread(target=guarded, name="bench-bring-up",
                     daemon=True).start()
    sys.argv = ["pushcdn-broker",
                *broker_argv(cfg, db, pub[me], priv[me], metrics[me])]
    from pushcdn_tpu.bin import broker
    try:
        broker.main()  # returns after SIGTERM's drain
    finally:
        for child in children.values():
            if child.poll() is None:
                child.send_signal(signal.SIGTERM)
        for child in children.values():
            try:
                child.wait(timeout=30)
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait()
    return 0


if __name__ == "__main__":
    sys.exit(main())
