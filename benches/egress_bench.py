#!/usr/bin/env python
"""Host egress hot-path bench: the socket side of the socket⇄HBM pump.

Three tiers, each one JSON line (medians of repeated trials, all trials
disclosed — the deployment core is shared, so single samples lie):

- ``egress/engine``: the native egress engine (`native.egress_encode`,
  framing.cpp) turning a step's delivery matrix into per-user wire
  streams — the ``host_egress_msgs_s`` number. Same
  shape as bench.py's companion row: 1024 user slots, 16384 frames x
  1 KB, 16 receivers per frame.
- ``egress/wire``: end-to-end host egress — pre-serialized frames fanned
  out to N in-process connections through the full coalescing writer
  (per-peer batch handoff -> adaptive coalesce -> native batch encode ->
  flush), counted at the receivers' transport drain.
- ``egress/writer_small_frames``: single-connection writer throughput on
   1 KB frames (the per-connection coalescing floor).

Usage: python benches/egress_bench.py [--quick]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from pushcdn_tpu import native
from pushcdn_tpu.proto.limiter import Bytes
from pushcdn_tpu.proto.transport.memory import (
    Memory,
    gen_testing_connection_pair,
)

RESULTS: list[dict] = []


def emit(name: str, value: float, unit: str, **extra) -> None:
    row = {"bench": name, "value": round(value, 1), "unit": unit, **extra}
    RESULTS.append(row)
    print(json.dumps(row), flush=True)


# ---------------------------------------------------------------------------
# tier 1: the native egress engine (the host_egress_msgs_s metric)
# ---------------------------------------------------------------------------

def bench_engine(trials: int) -> None:
    if not native.available():
        emit("egress/engine", 0, "skipped", reason="native lib unavailable")
        return
    U, S, F, FANOUT = 1024, 16384, 1024, 16
    rng = np.random.default_rng(1)
    deliver = np.zeros((U, S), bool)
    for f in range(S):
        deliver[rng.integers(0, U, FANOUT), f] = True
    lengths = np.full(S, F, np.int32)
    block = rng.integers(0, 256, (S, F)).astype(np.uint8)
    blocks = [block]

    streams = native.egress_encode(deliver, lengths, blocks)  # warm + pool
    total_msgs = streams.total_msgs
    rates = []
    for _ in range(trials):
        del streams  # return the pooled buffer before re-encoding
        t0 = time.perf_counter()
        streams = native.egress_encode(deliver, lengths, blocks)
        rates.append(total_msgs / (time.perf_counter() - t0))
    emit("egress/engine", statistics.median(rates), "msgs/s",
         users=U, frames=S, frame=F, fanout=FANOUT,
         trials=[round(r, 1) for r in rates],
         max=round(max(rates), 1))


# ---------------------------------------------------------------------------
# tier 2: end-to-end wire egress through the coalescing writer
# ---------------------------------------------------------------------------

async def bench_wire(receivers: int, msgs: int, trials: int) -> None:
    from pushcdn_tpu.proto.transport.base import FrameChunk

    pairs = [await gen_testing_connection_pair() for _ in range(receivers)]
    payload = os.urandom(1024)
    frame = Bytes(payload)

    async def drain(conn, n):
        got = 0
        async with asyncio.timeout(60):
            while got < n:
                for item in await conn.recv_frames(n - got):
                    got += item.remaining if type(item) is FrameChunk else 1
                    item.release()

    rates = []
    batch = 32  # frames handed per peer per wakeup (the routing loops'
    #             per-batch shape at sustained load)
    msgs = (msgs // batch) * batch  # drains must match sends exactly
    for _ in range(trials):
        t0 = time.perf_counter()
        drains = [asyncio.create_task(drain(rx, msgs))
                  for _tx, rx in pairs]
        for _ in range(msgs // batch):
            for tx, _rx in pairs:
                await tx.send_raw_many(
                    [frame.clone() for _ in range(batch)])
            await asyncio.sleep(0)
        await asyncio.gather(*drains)
        rates.append(msgs * receivers / (time.perf_counter() - t0))
    for tx, rx in pairs:
        tx.close()
        rx.close()
    emit("egress/wire", statistics.median(rates), "msgs/s",
         receivers=receivers, msgs_per_receiver=msgs, frame=1024,
         trials=[round(r, 1) for r in rates], max=round(max(rates), 1))


async def bench_writer_small_frames(msgs: int, trials: int) -> None:
    from pushcdn_tpu.proto.transport.base import FrameChunk

    tx, rx = await gen_testing_connection_pair()
    payload = os.urandom(1024)

    async def drain(n):
        got = 0
        async with asyncio.timeout(60):
            while got < n:
                for item in await rx.recv_frames(n - got):
                    got += item.remaining if type(item) is FrameChunk else 1
                    item.release()

    rates = []
    for _ in range(trials):
        t0 = time.perf_counter()
        d = asyncio.create_task(drain(msgs))
        for _ in range(msgs):
            await tx.send_raw(payload)
        await d
        rates.append(msgs / (time.perf_counter() - t0))
    tx.close()
    rx.close()
    emit("egress/writer_small_frames", statistics.median(rates), "msgs/s",
         frame=1024, msgs=msgs,
         trials=[round(r, 1) for r in rates], max=round(max(rates), 1))


async def amain(quick: bool) -> None:
    from pushcdn_tpu.bin.common import tune_gc
    tune_gc()
    bench_engine(trials=3 if quick else 5)
    prev = Memory.set_duplex_window(256 * 1024)
    try:
        await bench_wire(receivers=8, msgs=2_000 if quick else 10_000,
                         trials=3)
        await bench_writer_small_frames(msgs=5_000 if quick else 20_000,
                                        trials=3)
    finally:
        Memory.set_duplex_window(prev)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    asyncio.run(amain(args.quick))


if __name__ == "__main__":
    main()
