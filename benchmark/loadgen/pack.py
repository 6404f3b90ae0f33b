#!/usr/bin/env python3
"""One client process of the load generator: real users over real TCP
(or TCP+TLS: ``--transport``, ``--scheme`` from the configuration) on one
asyncio loop, through ``pushcdn_tpu.client``'s public API only.

Copied from ``pushcdn_tpu/testing/clientpack.py`` and extended: every
payload opens with the benchmark's own header (``loadgen/plan.py``), so a
subscriber times each delivery from when the frame was due, accounts it
per (publisher, stream), checks that a direct was meant for it and
compares a sample of payloads byte for byte. Publishers run one of three
loops, all parameters of the traffic file:

- ``open``      a Poisson process's gaps at a fixed rate, timed from due:
                the same number of frames, gaps and sizes for every seed,
                in another order (``plan.arrivals``, ``plan.mix_block``);
- ``windowed``  back to back, at most ``window`` frames ahead of the last
                probe the broker echoed (every ``probe_every``-th frame is
                a small direct to the publisher itself);
- ``echo``      one direct to itself, wait for it, think, again.

Protocol with the parent: one JSON object per line. In: ``connect``
(a placement group), ``go`` (the window), ``mark``, ``finish``. Out:
``hello``, ``ready``, ``sent``, ``mark``, ``result``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.loadgen import plan  # noqa: E402
from benchmark.loadgen.gaps import GapDetector  # noqa: E402
from benchmark.loadgen.hist import LogHistogram  # noqa: E402

CONNECT_CONCURRENCY = 25  # clientpack's default
# The warm-up opens with a prelude by the harness's own user
# (``plan.PRELUDE_FLOW``) while the cell's publishers hold: a burst that
# needs the step program over the full lanes, then one small enough for the
# latency-slice program, so that both are compiled (or loaded) before the
# window whatever the cell's traffic does later. Each burst's step is over
# long before the cell's flows start, so they start on an idle plane.
PRELUDE_GAP_S = 0.35
SAMPLE_MASK = 31          # every 32nd unique delivery is compared in full


def emit(event: str, **fields) -> None:
    print(json.dumps({"event": event, **fields}), flush=True)


class Window:
    """What this process saw of one measured window."""

    def __init__(self, warm_ns: int, start_ns: int, end_ns: int):
        self.warm_ns, self.start_ns, self.end_ns = warm_ns, start_ns, end_ns
        self.span_ns = end_ns - start_ns
        self.latency = LogHistogram()   # frames due inside
        self.late = LogHistogram()      # open loops: actual send - due
        self.received = 0     # unique deliveries that arrived inside
        self.due_inside = 0   # unique deliveries of frames due inside
        # mean latency by quarter of the window: a backlog that grows
        # shows as a last quarter slower than the first
        self.quarter_sum = [0, 0, 0, 0]
        self.quarter_n = [0, 0, 0, 0]


class User:
    def __init__(self, index: int, client):
        self.index = index
        self.client = client
        self.detector = GapDetector()
        self.task: Optional[asyncio.Task] = None
        self.unique = 0
        self.foreign = 0       # no benchmark header
        self.misdirected = 0   # a direct meant for someone else
        self.corrupt = 0       # sampled payload differs
        self.errors = 0        # receive errors (a lost connection)


class Publisher:
    def __init__(self, pub: int, user: User, flow: dict, frames):
        self.pub, self.user, self.flow, self.frames = pub, user, flow, frames
        self.seqs: Dict[int, int] = {}
        self.sent = 0
        self.before_start = 0  # frames due before the window
        self.before_end = 0    # frames due before its end
        self.errors = 0
        self.acked = 0         # frames covered by the last echoed probe
        self.echoed = -1       # last direct-to-self seen (echo loop)
        self.event = asyncio.Event()
        self.task: Optional[asyncio.Task] = None


class Pack:
    def __init__(self, args, traffic: dict):
        from pushcdn_tpu.bin.common import scheme_by_name, transport_by_name
        from pushcdn_tpu.client import Client, ClientConfig
        from pushcdn_tpu.proto.message import Broadcast, Direct
        self._broadcast, self._direct = Broadcast, Direct
        protocol = transport_by_name(args.transport)
        self.scheme = scheme_by_name(args.scheme)
        self._client = lambda **kw: Client(
            ClientConfig(protocol=protocol, scheme=self.scheme, **kw))
        self.args = args
        self.layout = plan.Layout(args.users, args.groups, args.sub_procs,
                                  args.pub_procs, traffic["flows"])
        self.pool = plan.make_pool(args.seed)
        self.table = plan.subscriptions(traffic["subscriptions"], args.users)
        self.mine = self.layout.users_of_proc(args.proc)
        self.users: List[User] = []                 # connected so far
        self.publishers: Dict[int, Publisher] = {}  # by user index
        self.window: Optional[Window] = None
        self.unique_total = 0
        self._keys: Dict[int, bytes] = {}

    def _keypair(self, user: int):
        return self.scheme.generate_keypair(
            seed=plan.key_seed(self.args.seed, user))

    def _public_key(self, user: int) -> bytes:
        key = self._keys.get(user)
        if key is None:
            key = self._keys[user] = self._keypair(user).public_key
        return key

    # ---- subscribers ------------------------------------------------------

    async def _receive(self, user: User) -> None:
        client = user.client
        observe = user.detector.observe
        me = user.index
        publisher = self.publishers.get(me)
        unpack = plan.HEADER.unpack_from
        head = plan.HEADER_BYTES
        publishers, pool = self.layout.publishers, self.pool
        while True:
            try:
                messages = await client.receive_messages()
            except asyncio.CancelledError:
                raise
            except Exception:
                # a lost connection loses frames; the reference will say so
                user.errors += 1
                await asyncio.sleep(0.05)
                continue
            now = time.monotonic_ns()
            w = self.window
            for m in messages:
                body = getattr(m, "message", None)
                if body is None or len(body) < head:
                    user.foreign += 1
                    continue
                pub, stream, seq, due, target = unpack(body)
                if pub >= publishers:
                    user.foreign += 1
                    continue
                if stream >= plan.STREAM_PROBE and target != me:
                    user.misdirected += 1
                if not observe(pub, stream, seq):
                    continue
                user.unique += 1
                self.unique_total += 1
                if user.unique & SAMPLE_MASK == 0:
                    fill = len(body) - head
                    off = plan.filler_offset(pub, stream, seq, fill)
                    if bytes(body[head:]) != pool[off:off + fill]:
                        user.corrupt += 1
                if w is not None:
                    if w.start_ns <= now < w.end_ns:
                        w.received += 1
                    if w.start_ns <= due < w.end_ns:
                        w.due_inside += 1
                        lat = now - due
                        w.latency.add(lat)
                        q = (due - w.start_ns) * 4 // w.span_ns
                        w.quarter_sum[q] += lat
                        w.quarter_n[q] += 1
                if publisher is not None and pub == publisher.pub \
                        and target == me:
                    if stream == plan.STREAM_PROBE:
                        publisher.acked = \
                            (seq + 1) * publisher.flow["loop"]["probe_every"]
                        publisher.event.set()
                    elif stream == plan.STREAM_DIRECT:
                        publisher.echoed = seq
                        publisher.event.set()

    # ---- publishers -------------------------------------------------------

    async def _send(self, p: Publisher, due_ns: int) -> int:
        """Send publisher ``p``'s next frame, stamped ``due_ns``; returns
        its sequence number."""
        frame = next(p.frames)
        key = frame.target if frame.kind == plan.BROADCAST \
            else (frame.kind << 16) + frame.target
        seq = p.seqs.get(key, 0)
        p.seqs[key] = seq + 1
        payload = plan.build_payload(self.pool, p.pub, frame, seq, due_ns)
        if frame.kind == plan.BROADCAST:
            message = self._broadcast(topics=[frame.target], message=payload)
        else:
            message = self._direct(recipient=self._public_key(frame.target),
                                   message=payload)
        try:
            await p.user.client.send_message(message)
        except asyncio.CancelledError:
            raise
        except Exception:
            p.errors += 1
        w = self.window
        p.sent += 1
        if due_ns < w.start_ns:
            p.before_start = p.sent
        if due_ns < w.end_ns:
            p.before_end = p.sent
        return seq

    @staticmethod
    async def _sleep_until(t_ns: int) -> None:
        """asyncio's timers round up to a millisecond: sleep short of the
        mark, then yield to the loop until it is reached."""
        delay = (t_ns - time.monotonic_ns()) / 1e9
        if delay > 0.002:
            await asyncio.sleep(delay - 0.0015)
        while time.monotonic_ns() < t_ns:
            await asyncio.sleep(0)

    async def _prelude(self, p: Publisher) -> None:
        await self._sleep_until(self.window.warm_ns)
        for burst in plan.PRELUDE_BURSTS:
            now = time.monotonic_ns()
            for _ in range(burst):  # back to back: one write, one batch
                await self._send(p, now)
            await asyncio.sleep(PRELUDE_GAP_S)

    async def _run_open(self, p: Publisher, t0_ns: int,
                        rate_per_s: float) -> None:
        w = self.window
        share = rate_per_s / p.flow["publishers"]
        # a fixed number of frames in the warm-up and in the window, the
        # same for every seed (``plan.arrivals``)
        for part, lo_ns, hi_ns in (("warm", t0_ns, w.start_ns),
                                   ("window", w.start_ns, w.end_ns)):
            for offset in plan.arrivals(self.args.seed, p.pub, share,
                                        hi_ns - lo_ns, part):
                due = lo_ns + offset
                await self._sleep_until(due)
                late = time.monotonic_ns() - due
                if due >= w.start_ns:
                    w.late.add(late if late > 0 else 1)
                await self._send(p, due)

    async def _run_windowed(self, p: Publisher, t0_ns: int) -> None:
        w = self.window
        window = p.flow["loop"]["window"]
        await self._sleep_until(t0_ns)
        while True:
            now = time.monotonic_ns()
            if now >= w.end_ns:
                return
            if p.sent - p.acked >= window:
                p.event.clear()
                try:
                    await asyncio.wait_for(
                        p.event.wait(), (w.end_ns - now) / 1e9 + 0.001)
                except asyncio.TimeoutError:
                    return
                continue
            await self._send(p, now)
            if p.sent & 15 == 0:
                # queueing never yields: let the writer flush and the
                # receive loop see the probes
                await asyncio.sleep(0)

    async def _run_echo(self, p: Publisher, t0_ns: int) -> None:
        w = self.window
        think_s = p.flow["loop"]["think_s"]
        await self._sleep_until(t0_ns)
        while True:
            now = time.monotonic_ns()
            if now >= w.end_ns:
                return
            p.event.clear()
            seq = await self._send(p, now)
            while p.echoed < seq:
                left = (w.end_ns - time.monotonic_ns()) / 1e9 + 1.0
                try:
                    await asyncio.wait_for(p.event.wait(), max(left, 0.001))
                except asyncio.TimeoutError:
                    return  # the echo is lost; the reference will say so
                p.event.clear()
            await asyncio.sleep(think_s)

    async def _publish(self, rate_override: Optional[float]) -> None:
        t0_ns = self.window.warm_ns + int(
            len(plan.PRELUDE_BURSTS) * PRELUDE_GAP_S * 1e9)
        tasks = []
        for p in self.publishers.values():
            loop = p.flow["loop"]
            kind = loop["kind"]
            if kind == "open":
                run = self._run_open(p, t0_ns,
                                     rate_override or loop["rate_per_s"])
            elif kind == "windowed":
                run = self._run_windowed(p, t0_ns)
            elif kind == "echo":
                run = self._run_echo(p, t0_ns)
            elif kind == "prelude":
                run = self._prelude(p)
            else:
                raise ValueError(f"unknown loop kind {kind!r}")
            p.task = asyncio.create_task(run)
            tasks.append(p.task)
        if tasks:
            await asyncio.gather(*tasks)
        emit("sent", publishers={
            str(p.pub): {"sent": p.sent, "before_start": p.before_start,
                         "before_end": p.before_end, "errors": p.errors}
            for p in self.publishers.values()})

    # ---- the parent's commands --------------------------------------------

    async def _connect(self, group: int, marshal: str) -> None:
        t0 = time.monotonic()
        mine = []
        for u in self.mine:
            if self.layout.group_of(u) != group:
                continue
            user = User(u, self._client(
                marshal_endpoint=marshal, keypair=self._keypair(u),
                subscribed_topics=set(self.table[u])))
            mine.append(user)
            pub = self.layout.pub_of_user.get(u)
            if pub is not None:
                flow = self.layout.flow_of_pub[pub]
                self.publishers[u] = Publisher(
                    pub, user, flow, plan.frame_plan(
                        self.args.seed, self.layout, flow, pub))
        self.users += mine
        gate = asyncio.Semaphore(CONNECT_CONCURRENCY)

        async def one(user: User) -> None:
            async with gate:
                await user.client.ensure_initialized()
            user.task = asyncio.create_task(self._receive(user))

        await asyncio.gather(*(one(u) for u in mine))
        emit("ready", group=group, users=len(mine),
             seconds=time.monotonic() - t0)

    def _result(self) -> dict:
        w = self.window
        out = {
            "users": {str(u.index): u.detector.report() for u in self.users},
            "foreign": sum(u.foreign for u in self.users),
            "misdirected": sum(u.misdirected for u in self.users),
            "corrupt": sum(u.corrupt for u in self.users),
            "receive_errors": sum(u.errors for u in self.users),
            "unique": self.unique_total,
        }
        if w is not None:
            out.update(latency=w.latency.counts, late=w.late.counts,
                       received=w.received, due_inside=w.due_inside,
                       quarter_sum=w.quarter_sum, quarter_n=w.quarter_n)
        return out

    async def run(self) -> int:
        emit("hello", proc=self.args.proc, users=len(self.mine))
        loop = asyncio.get_running_loop()
        publishing: Optional[asyncio.Task] = None
        while True:
            line = await loop.run_in_executor(None, sys.stdin.readline)
            if not line:
                break  # the parent went away
            cmd = json.loads(line)
            name = cmd["cmd"]
            if name == "connect":
                await self._connect(cmd["group"], cmd["marshal"])
            elif name == "go":
                if publishing is not None:
                    await publishing
                self.window = Window(cmd["warm_ns"], cmd["start_ns"],
                                     cmd["end_ns"])
                for p in self.publishers.values():
                    p.before_start = p.before_end = p.sent
                publishing = asyncio.create_task(
                    self._publish(cmd.get("rate_per_s")))
            elif name == "mark":
                emit("mark", unique=self.unique_total)
            elif name == "report":
                emit("result", **self._result())
            elif name == "finish":
                break
        if publishing is not None:
            publishing.cancel()
            await asyncio.gather(publishing, return_exceptions=True)
        tasks = [u.task for u in self.users if u.task is not None]
        tasks += [p.task for p in self.publishers.values()
                  if p.task is not None]
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        for u in self.users:
            u.client.close()
        return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True, help="traffic file (JSON)")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--proc", type=int, required=True)
    ap.add_argument("--users", type=int, required=True)
    ap.add_argument("--groups", type=int, required=True)
    ap.add_argument("--sub-procs", type=int, required=True)
    ap.add_argument("--pub-procs", type=int, required=True)
    ap.add_argument("--transport", default="tcp",
                    help="the users' transport, by the program's name")
    ap.add_argument("--scheme", default="ed25519",
                    help="the users' signature scheme, by the program's name")
    args = ap.parse_args()
    with open(args.traffic) as f:
        traffic = json.load(f)

    async def amain() -> int:
        return await Pack(args, traffic).run()

    try:
        return asyncio.run(amain())
    except KeyboardInterrupt:
        return 130


if __name__ == "__main__":
    sys.exit(main())
