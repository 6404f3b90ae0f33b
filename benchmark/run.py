#!/usr/bin/env python3
"""The benchmark: one cell, one run.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that starts the cell's deployment (its configuration's
launcher, which owns the chip), connects the users (``loadgen/pack.py``
processes), warms up with the cell's own traffic, measures for
``--seconds``, drains, holds what arrived to the plain reference
(``reference.py``), stops everything (SIGTERM and wait) and prints one
JSON object as its last line. This parent never imports jax.

Set-up, all of it inside ``setup_s``: native libraries (built once into
``.build/``), the deployment, the users, then ``WARM_S`` seconds of the
cell's traffic so that the step programs the cell uses are compiled (or
loaded from the persistent cache) before the window; a program obtained
inside the window fails ``correct``.

``--trace 1`` also has the launcher trace the warm-up and the first
``TRACE_S`` seconds of the window (the warm-up's prelude drives the device
in every cell, also in one whose own traffic the program host-routes); its
file is reduced by ``trace_reduce.py`` in a child, and the line carries
the cell's per-layer metrics instead of its end-to-end ones. A run with
an explicit ``JAX_PLATFORMS=cpu`` is the dry run: it says ``"platform":
"cpu"`` and is never a result. Without it a machine with no accelerator
gives a non-zero exit code and no result.
"""

from __future__ import annotations

import time

T0_NS = time.monotonic_ns()  # as near to process start as Python allows

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

WARM_S = 2.0      # the cell's own traffic before the window
TRACE_S = 3.0     # how far into the window the traced span reaches
DRAIN_S = 5.0     # how long after the window a delivery may still arrive
CLK_TCK = os.sysconf("SC_CLK_TCK")


class Failure(Exception):
    """The run cannot produce a result."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def cpu_seconds(pid: int) -> float:
    """User + system CPU of a process, all its threads, so far."""
    with open(f"/proc/{pid}/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    return (int(fields[11]) + int(fields[12])) / CLK_TCK


async def sleep_until(t_ns: int) -> None:
    delay = (t_ns - time.monotonic_ns()) / 1e9
    if delay > 0:
        await asyncio.sleep(delay)


class Child:
    """A child that speaks JSON lines: events out, commands in."""

    def __init__(self, name: str, proc: asyncio.subprocess.Process):
        self.name, self.proc = name, proc
        self.events: asyncio.Queue = asyncio.Queue()
        self.early: List[dict] = []  # events nobody has asked for yet
        self.reader = asyncio.create_task(self._read())

    @classmethod
    async def spawn(cls, name: str, argv: List[str],
                    log_path: str) -> "Child":
        with open(log_path, "ab") as log:
            proc = await asyncio.create_subprocess_exec(
                *argv, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=log, cwd=REPO, limit=1 << 26)
        return cls(name, proc)

    async def _read(self) -> None:
        while True:
            line = await self.proc.stdout.readline()
            if not line:
                await self.events.put({"event": "eof"})
                return
            try:
                await self.events.put(json.loads(line))
            except ValueError:
                pass  # a stray print is not protocol

    async def expect(self, event: str, timeout: float) -> dict:
        for i, ev in enumerate(self.early):
            if ev["event"] == event:
                return self.early.pop(i)
        try:
            async with asyncio.timeout(timeout):
                while True:
                    ev = await self.events.get()
                    if ev["event"] == event:
                        return ev
                    if ev["event"] in ("eof", "error"):
                        raise Failure(f"{self.name}: {ev} while waiting "
                                      f"for {event!r}")
                    self.early.append(ev)
        except TimeoutError:
            raise Failure(f"{self.name}: no {event!r} within {timeout:.0f}s")

    async def send(self, cmd: str, **fields) -> None:
        self.proc.stdin.write(json.dumps({"cmd": cmd, **fields}).encode()
                              + b"\n")
        await self.proc.stdin.drain()

    async def ask(self, cmd: str, event: str, timeout: float = 30.0,
                  **fields) -> dict:
        await self.send(cmd, **fields)
        return await self.expect(event, timeout)

    async def stop(self, grace_s: float = 60.0) -> Optional[int]:
        """SIGTERM and wait — never kill a chip owner that can still
        answer (a killed owner leaves the libtpu lock behind)."""
        self.reader.cancel()
        if self.proc.returncode is None:
            try:
                self.proc.send_signal(signal.SIGTERM)
            except ProcessLookupError:
                pass
            try:
                await asyncio.wait_for(self.proc.wait(), grace_s)
            except asyncio.TimeoutError:
                say(f"{self.name} ignored SIGTERM for {grace_s:.0f}s; killing")
                self.proc.kill()
                await self.proc.wait()
        return self.proc.returncode


class Run:
    """One cell's deployment, users and windows."""

    def __init__(self, cell, args, workdir: str):
        from benchmark.loadgen import plan
        self.cell, self.args, self.workdir = cell, args, workdir
        cfg = cell.config
        self.users = cfg["users"]
        self.sub_procs = cfg["client_processes"]["subscribers"]
        self.pub_procs = cfg["client_processes"]["publishers"]
        if args.test_size:
            self.users, self.sub_procs, self.pub_procs = (
                int(x) for x in args.test_size.split(","))
        self.groups = cfg["placement_groups"]
        self.layout = plan.Layout(self.users, self.groups, self.sub_procs,
                                  self.pub_procs, cell.traffic["flows"])
        self.launcher: Optional[Child] = None
        self.packs: List[Child] = []
        self.ready: dict = {}
        self.setup: Dict[str, float] = {}
        # what each publisher has sent so far, over all windows
        self.sent = [0] * self.layout.publishers

    # ---- bring-up ---------------------------------------------------------

    async def start(self) -> None:
        from benchmark import manifest
        cfg = self.cell.config
        clients = manifest.client_settings(cfg)
        t_spawn = time.monotonic_ns()
        self.launcher = await Child.spawn(
            "launcher",
            [sys.executable,
             os.path.join(REPO, manifest.launcher_path(cfg["launcher"])),
             "--config", self.cell.config_file, "--workdir", self.workdir],
            os.path.join(self.workdir, "launcher.log"))
        for proc in range(self.layout.procs):
            self.packs.append(await Child.spawn(
                f"pack{proc}",
                [sys.executable, os.path.join(BENCH, "loadgen", "pack.py"),
                 "--traffic", self.cell.traffic_file,
                 "--seed", str(self.args.seed), "--proc", str(proc),
                 "--users", str(self.users), "--groups", str(self.groups),
                 "--sub-procs", str(self.sub_procs),
                 "--pub-procs", str(self.pub_procs),
                 "--transport", clients["user_transport"],
                 "--scheme", clients["signature_scheme"]],
                os.path.join(self.workdir, f"pack{proc}.log")))
        # the first run in a checkout compiles: the contract gives it 1200 s
        self.ready = await self.launcher.expect("ready", 900)
        self.setup["broker_ready_s"] = \
            (self.ready["plane_ready_ns"] - t_spawn) / 1e9
        device = self.ready["device"]
        say(f"deployment up in {(time.monotonic_ns() - t_spawn) / 1e9:.2f}s "
            f"(plane ready {self.setup['broker_ready_s']:.2f}s) on {device}; "
            f"{self.ready.get('plane')}; compile cache "
            f"{self.ready.get('compile_cache')}")
        if device["count"] < self.cell.workload["chips"]:
            raise Failure(f"the cell needs {self.cell.workload['chips']} "
                          f"chips, JAX shows {device['count']}")
        for pack in self.packs:
            await pack.expect("hello", 120)
        t_connect = time.monotonic_ns()
        for group in range(self.groups):
            await self.launcher.ask("place", "placed", group=group)
            for pack in self.packs:
                await pack.send("connect", group=group,
                                marshal=self.ready["marshal"])
            for pack in self.packs:
                await pack.expect("ready", 300)
        self.setup["connect_s"] = (time.monotonic_ns() - t_connect) / 1e9
        say(f"{self.users} users connected in {self.setup['connect_s']:.2f}s")

    async def counters(self) -> dict:
        """Everything the launcher says: the program's own counters under
        its names (``control.scalars``) and the launcher's beside them.
        The launcher asks a busy broker for ``TOPOLOGY_WAIT_S`` at most."""
        from benchmark.launchers import control
        return await self.launcher.ask(
            "counters", "counters", control.TOPOLOGY_WAIT_S + 15)

    # ---- one window -------------------------------------------------------

    async def window(self, seconds: float, warm_s: float, trace: bool,
                     rate_per_s: Optional[float] = None) -> SimpleNamespace:
        """Warm up, measure, drain, collect. Returns everything the
        metrics are read from."""
        from benchmark import reference
        from benchmark.loadgen import plan
        from benchmark.loadgen.hist import LogHistogram
        w = SimpleNamespace(seconds=seconds, counters={}, trace=None)
        w.counters["before"] = await self.counters()
        if trace:
            await self.launcher.ask(
                "trace", "trace_armed", 90,
                seconds=0.3 + warm_s + min(TRACE_S, seconds),
                dir=os.path.join(self.workdir, "trace"))
        w.warm_ns = time.monotonic_ns() + 300_000_000
        w.start_ns = w.warm_ns + int(warm_s * 1e9)
        w.end_ns = w.start_ns + int(seconds * 1e9)
        go = dict(warm_ns=w.warm_ns, start_ns=w.start_ns, end_ns=w.end_ns)
        if rate_per_s is not None:
            go["rate_per_s"] = rate_per_s
        for pack in self.packs:
            await pack.send("go", **go)
        # after the warm-up's compiles, before the window
        await sleep_until(w.start_ns - 400_000_000)
        w.counters["warm"] = await self.counters()
        pids = {"route": self.ready["route_pids"],
                "packs": [p.proc.pid for p in self.packs]}
        await sleep_until(w.start_ns)
        if trace:  # costs the broker a request: traced runs only
            w.counters["start"] = await self.counters()
        cpu0 = {k: [cpu_seconds(p) for p in v] for k, v in pids.items()}
        t_cpu0 = time.monotonic_ns()
        await sleep_until(w.end_ns)
        cpu1 = {k: [cpu_seconds(p) for p in v] for k, v in pids.items()}
        w.cpu_wall_s = (time.monotonic_ns() - t_cpu0) / 1e9
        w.cpu = {k: [b - a for a, b in zip(cpu0[k], cpu1[k])] for k in pids}
        if trace:
            # not a request to the chip's owner while it stops the
            # profiler: the two have been seen to block each other
            w.traced = await self.launcher.expect("traced", 120)
        w.counters["end"] = await self.counters()

        # what was published, and what the plain reference owes for it
        w.publish_errors = 0
        first = list(self.sent)
        inside = {}
        for pack in self.packs:
            ev = await pack.expect("sent", seconds + warm_s + 60)
            for pub, row in ev["publishers"].items():
                pub = int(pub)
                inside[pub] = (row["before_start"], row["before_end"])
                self.sent[pub] = row["sent"]
                w.publish_errors += row["errors"]
        t_ref = time.monotonic()
        table = plan.subscriptions(self.cell.traffic["subscriptions"],
                                   self.users)
        log_all, log_window = [], []
        for pub in range(self.layout.publishers):
            frames = plan.frame_plan(
                self.args.seed, self.layout, self.layout.flow_of_pub[pub],
                pub)
            lo, hi = inside[pub]
            for k in range(self.sent[pub]):
                frame = next(frames)
                entry = (pub, frame.kind, frame.target)
                log_all.append(entry)
                if lo <= k < hi:
                    log_window.append(entry)
        # the cell's own frames: the last publisher is the harness's
        # prelude, whose bursts the plane always stages
        prelude = self.sent[-1] - first[-1]
        w.frames_sent = len(log_all) - sum(first) - prelude
        w.frames_inside = len(log_window)
        w.owed = reference.route(table, log_all)
        w.attempted = reference.total(reference.route(table, log_window))
        owed_total = reference.total(w.owed)
        say(f"reference: {len(log_all)} frames so far owe {owed_total} "
            f"deliveries ({w.frames_inside} frames due in the window owe "
            f"{w.attempted}), worked out in {time.monotonic() - t_ref:.2f}s")

        # a fixed drain: stop early only once everything owed has arrived
        while True:
            marks = [await p.ask("mark", "mark") for p in self.packs]
            unique = sum(m["unique"] for m in marks)
            if unique >= owed_total or \
                    time.monotonic_ns() > w.end_ns + int(DRAIN_S * 1e9):
                break
            await asyncio.sleep(0.2)
        w.drain_s = (time.monotonic_ns() - w.end_ns) / 1e9
        results = [await p.ask("report", "result", 120) for p in self.packs]
        w.counters["final"] = await self.counters()
        w.frames_staged = w.counters["final"]["frames_staged"] \
            - w.counters["before"]["frames_staged"] - prelude

        reports: List[dict] = [{} for _ in range(self.users)]
        w.latency, w.late = LogHistogram(), LogHistogram()
        w.received = w.due_inside = w.duplicates = w.unique = 0
        w.quarter_sum, w.quarter_n = [0] * 4, [0] * 4
        w.client_faults = {k: 0 for k in (
            "foreign", "misdirected", "corrupt", "receive_errors")}
        for res in results:
            for user, report in res["users"].items():
                reports[int(user)] = report
                w.duplicates += sum(row[4] for row in report.values())
            w.latency.merge(LogHistogram(res["latency"]))
            w.late.merge(LogHistogram(res["late"]))
            w.received += res["received"]
            w.due_inside += res["due_inside"]
            w.unique += res["unique"]
            for q in range(4):
                w.quarter_sum[q] += res["quarter_sum"][q]
                w.quarter_n[q] += res["quarter_n"][q]
            for k in w.client_faults:
                w.client_faults[k] += res[k]
        w.problems = reference.compare(w.owed, reports)
        w.failed = max(w.attempted - w.due_inside, 0) + w.publish_errors
        return w

    async def stop(self) -> Optional[int]:
        for pack in self.packs:
            if pack.proc.returncode is None:
                try:
                    await pack.send("finish")
                except (ConnectionError, OSError):
                    pass
        for pack in self.packs:
            try:
                await asyncio.wait_for(pack.proc.wait(), 30)
            except asyncio.TimeoutError:
                pass
            await pack.stop(10)
        rc = None
        if self.launcher is not None:
            rc = await self.launcher.stop()
        return rc


def reduce_trace(cell, traced: dict) -> Optional[dict]:
    """Reduce the launcher's trace in a child that may import jax (held
    to the CPU: it reads a file, it needs no device)."""
    if not traced.get("file"):
        return None
    argv = [sys.executable, os.path.join(BENCH, "trace_reduce.py"),
            traced["file"]]
    for name in cell.config.get("step_modules", []):
        argv += ["--step-module", name]
    for name in cell.config.get("kernels", {}).values():
        argv += ["--kernel", name]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(argv, capture_output=True, text=True, env=env,
                          timeout=300, cwd=REPO)
    if proc.returncode != 0:
        say(f"trace reduction failed: {proc.stderr[-2000:]}")
        return None
    reduced = json.loads(proc.stdout.strip().splitlines()[-1])
    reduced["window_s"] = traced["window_s"]
    reduced["bytes"] = os.path.getsize(traced["file"])
    return reduced


def end_to_end(w, setup_s: float) -> Dict[str, float]:
    """Every end-to-end number this run can give; the cell's manifest
    entries choose which are reported."""
    route_cpu = sum(w.cpu["route"])
    p50, p99 = w.latency.percentile(50), w.latency.percentile(99)
    return {
        "delivered_per_s": w.received / w.seconds,
        "delivery_p50_ms": p50 / 1e6 if p50 else None,
        "delivery_p99_ms": p99 / 1e6 if p99 else None,
        "broker_cpu_us_per_delivery":
            route_cpu * 1e6 / w.received if w.received else None,
        "setup_s": setup_s,
    }


def compared(w, run: Run, launcher_rc: Optional[int],
             cpu_dry_run: bool) -> Dict[str, list]:
    """Every number ``correct`` rests on beside its limit, as ``[number,
    limit]``. Each comparison is exact: the run is correct when every
    number equals its limit. The plain reference (``reference.py``, all
    users, every frame) gives the first; the clients, the launcher and
    the program's own counters the rest."""
    c = w.counters
    platform = run.ready["device"]["platform"]
    return {
        "streams_differing": [len(w.problems), 0],
        "deliveries_missing": [w.failed, 0],
        "publish_errors": [w.publish_errors, 0],
        **{k: [v, 0] for k, v in w.client_faults.items()},
        "plane_disabled": [int(bool(c["final"]["disabled"])), 0],
        "programs_in_window":
            [c["end"]["programs"] - c["warm"]["programs"], 0],
        "users_connected": [c["before"]["users"], run.users],
        "users_unmirrored": [c["before"]["unmirrored"], 0],
        "launcher_exit_code": [launcher_rc, 0],
        "accelerator_missing":
            [int(platform == "cpu" and not cpu_dry_run), 0],
    }


async def run_cell(cell, args, workdir: str, cpu_dry_run: bool) -> int:
    from benchmark import manifest
    run = Run(cell, args, workdir)
    trace = bool(args.trace)
    launcher_rc = None
    try:
        await run.start()
        if args.sweep:
            return await sweep(run, args)
        w = await run.window(args.seconds, WARM_S, trace)
    finally:
        launcher_rc = await run.stop()
    setup_s = (w.start_ns - T0_NS) / 1e9
    if trace:
        w.trace = reduce_trace(cell, w.traced)
    say(f"set-up {setup_s:.2f}s: " + ", ".join(
        f"{k} {v:.2f}" for k, v in run.setup.items())
        + f", warm-up {WARM_S:.1f}; drain {w.drain_s:.2f}s")
    say(f"window: {w.frames_inside} frames due, {w.received} deliveries "
        f"received, {w.latency.n} latency samples, {w.duplicates} "
        f"duplicates; cpus {os.cpu_count()}; route CPU "
        f"{sum(w.cpu['route']):.2f}s of {w.cpu_wall_s:.2f}s; client CPU "
        + " ".join(f"{c:.2f}" for c in w.cpu["packs"]))
    say("counters: " + json.dumps({k: {f: v.get(f) for f in (
        "steps", "frames_staged", "messages_routed", "programs",
        "cache_hits", "cache_misses", "compile_s")}
        for k, v in w.counters.items()}))
    say("counters at the end, every key: " + json.dumps(w.counters["final"]))
    quarters = [s / n / 1e6 if n else None
                for s, n in zip(w.quarter_sum, w.quarter_n)]
    say(f"mean latency by quarter of the window (ms): {quarters}")
    checks = compared(w, run, launcher_rc, cpu_dry_run)
    for problem in w.problems[:5]:  # which streams: ``checks`` only counts
        say(f"differs from the reference: {problem}")

    numbers = end_to_end(w, setup_s)
    say("end to end" + (" (traced run: not reported as metrics)"
                        if trace else "") + f": {numbers}")
    device = dict(run.ready["device"])
    device["memory_peak_bytes"] = w.counters["final"]["memory_peak_bytes"]
    line = {"correct": all(number == limit
                           for number, limit in checks.values()),
            "attempted": w.attempted, "failed": w.failed}
    if trace:
        info = SimpleNamespace(
            window=w, setup=run.setup, device=device, config=cell.config,
            traffic=cell.traffic)
        metrics = {}
        for m in cell.per_layer:
            value = manifest.layer_metric(REPO, m["name"]).read(info)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if w.trace:
            device["busy_s"] = w.trace["busy_s"]
            device["window_s"] = w.trace["window_s"]
            line["breakdown"] = {"device_ops": w.trace["device_ops"],
                                 "idle_gaps": w.trace["idle_gaps"]}
            shown = {k: v for k, v in w.trace.items()
                     if k not in ("device_ops", "idle_gaps", "kernels")}
            shown["kernels"] = {k: [row["count"], row["seconds"]]
                                for k, row in w.trace["kernels"].items()}
            say(f"trace: {json.dumps(shown)}; "
                f"start_trace took {w.traced['start_call_s']:.3f}s, "
                f"stop_trace {w.traced['stop_call_s']:.3f}s")
    else:
        metrics = {}
        for m in cell.end_to_end:
            value = numbers.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = device
    # the verdict's one rendering is ``checks``, last in the line; the
    # contract has the same numbers as standard error's last lines, where
    # the driver's record of a run that is not correct keeps them
    line["checks"] = checks
    if "jax" in sys.modules:
        raise Failure("the benchmark's parent imported jax")
    sys.stdout.flush()
    for name, (number, limit) in checks.items():
        print(f"check {name}: {number} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


async def sweep(run: Run, args) -> int:
    """Offer each rate in turn to one deployment (open-loop flows only)
    and print what came of it: the knee is read off these lines by hand,
    once, and written into the traffic file."""
    for rate in (float(x) for x in args.sweep.split(",")):
        w = await run.window(args.seconds, 1.0, False, rate_per_s=rate)
        p50, p99 = w.latency.percentile(50), w.latency.percentile(99)
        late = w.late.percentile(99)
        quarters = [round(s / n / 1e6, 3) if n else None
                    for s, n in zip(w.quarter_sum, w.quarter_n)]
        c = w.counters
        print(json.dumps({
            "sweep_rate_per_s": rate, "frames": w.frames_inside,
            "offered_per_s": w.frames_inside / w.seconds,
            "delivered_per_s": w.received / w.seconds,
            "failed": w.failed, "attempted": w.attempted,
            "p50_ms": p50 / 1e6 if p50 else None,
            "p99_ms": p99 / 1e6 if p99 else None,
            "quarter_mean_ms": quarters,
            "gen_late_p99_ms": late / 1e6 if late else None,
            "broker_cpu_cores": sum(w.cpu["route"]) / w.cpu_wall_s,
            "pack_cpu_cores_max": max(w.cpu["packs"]) / w.cpu_wall_s,
            "staged_share": w.frames_staged / max(w.frames_sent, 1),
            "steps": c["final"]["steps"] - c["before"]["steps"],
            "drain_s": w.drain_s, "problems": len(w.problems),
        }), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="",
                    help="comma-separated rates (frames/s) to offer in turn "
                         "to one deployment; prints one line per rate and "
                         "no result")
    ap.add_argument("--test-size", default="",
                    help="tests only: users,subscriber processes,publisher "
                         "processes in place of the configuration's")
    ap.add_argument("--keep", action="store_true",
                    help="keep the work directory (logs, trace)")
    args = ap.parse_args()
    try:
        from benchmark import manifest
        from pushcdn_tpu import native
    except ImportError as exc:
        print(f"benchmark: not in a checkout of the repo ({exc})",
              file=sys.stderr)
        return 2
    cell = manifest.find_cell(args.workload)
    if cell is None:
        print(f"benchmark: no workload {args.workload!r}", file=sys.stderr)
        return 2
    cpu_dry_run = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    t0 = time.monotonic()
    libs = native.build_all()
    say(f"native libraries ({time.monotonic() - t0:.2f}s): "
        f"{sum(map(bool, libs.values()))} of {len(libs)} built")
    if not all(libs.values()):
        print(f"benchmark: native build failed: {libs}", file=sys.stderr)
        return 2
    workdir = tempfile.mkdtemp(prefix="pushcdn-bench-")
    try:
        rc = asyncio.run(run_cell(cell, args, workdir, cpu_dry_run))
    except Failure as exc:
        print(f"benchmark: {exc} (logs under {workdir})", file=sys.stderr)
        try:
            with open(os.path.join(workdir, "launcher.log"),
                      errors="replace") as f:
                sys.stderr.write(f.read()[-3000:])
        except OSError:
            pass
        return 1
    if not args.keep:
        shutil.rmtree(workdir, ignore_errors=True)
    else:
        say(f"work directory kept: {workdir}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
