#!/usr/bin/env python
"""HotShot-consensus-shaped traffic replay through the device router
(BASELINE.json configs[4]: "HotShot-consensus traffic replay, 10k validator
keys, full-pod broadcast").

The reference exists to carry HotShot consensus traffic: per view, a leader
broadcasts a proposal to every validator (the `Global` topic), validators
send votes as direct messages to the next leader, and a DA committee
exchanges data-availability traffic on the `DA` topic. This bench
synthesizes that shape — 10k validator slots, view-by-view — and replays
it through the single-chip routing step, measuring consensus messages
routed per second.

Usage: python benches/consensus_replay.py [--views 50] [--validators 10000]
Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from pushcdn_tpu.parallel.crdt import CrdtState
from pushcdn_tpu.parallel.router import (
    IngressBatch,
    RouterState,
    routing_step,
)
from pushcdn_tpu.proto.message import KIND_BROADCAST, KIND_DIRECT

TOPIC_GLOBAL, TOPIC_DA = 0, 1
FRAME = 512           # proposal/vote frames are small
DA_COMMITTEE = 64     # parity with the 4×64 topic config shape


def build_view_batch(view: int, validators: int, slots: int,
                     rng: np.random.Generator) -> IngressBatch:
    """One consensus view's ingress: 1 proposal broadcast + `validators`
    votes (direct to the next leader) + DA chatter, padded to `slots`."""
    leader = (view + 1) % validators
    frame_bytes = rng.integers(0, 256, (slots, FRAME)).astype(np.uint8)
    kind = np.zeros(slots, np.int32)
    length = np.full(slots, FRAME, np.int32)
    topic_mask = np.zeros(slots, np.uint32)
    dest = np.full(slots, -1, np.int32)
    valid = np.zeros(slots, bool)

    # proposal: full-pod broadcast on Global
    kind[0] = KIND_BROADCAST
    topic_mask[0] = 1 << TOPIC_GLOBAL
    valid[0] = True
    # DA proposal on the DA topic
    kind[1] = KIND_BROADCAST
    topic_mask[1] = 1 << TOPIC_DA
    valid[1] = True
    # votes: direct to next leader (as many as fit this batch)
    nvotes = min(validators, slots - 2)
    kind[2:2 + nvotes] = KIND_DIRECT
    dest[2:2 + nvotes] = leader
    valid[2:2 + nvotes] = True

    return IngressBatch(
        jnp.asarray(frame_bytes), jnp.asarray(kind), jnp.asarray(length),
        jnp.asarray(topic_mask), jnp.asarray(dest), jnp.asarray(valid))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--views", type=int, default=50)
    ap.add_argument("--validators", type=int, default=10_000)
    ap.add_argument("--slots", type=int, default=16384,
                    help="ingress slots per step (default fits a whole "
                         "10k-validator view: proposal + DA + every vote)")
    args = ap.parse_args()

    V = args.validators
    # every validator subscribes Global; the DA committee also subscribes DA
    masks = np.full(V, 1 << TOPIC_GLOBAL, np.uint32)
    masks[:DA_COMMITTEE] |= 1 << TOPIC_DA
    state = RouterState(
        crdt=CrdtState(
            owners=jnp.zeros(V, jnp.int32),
            versions=jnp.ones(V, jnp.uint32),
            identities=jnp.zeros(V, jnp.int32)),
        topic_masks=jnp.asarray(masks))

    rng = np.random.default_rng(0)
    batches = [build_view_batch(v, V, args.slots, rng)
               for v in range(min(args.views, 8))]  # reuse shapes, rotate

    # Every view's delivery matrix is consumed ON DEVICE, INSIDE ONE jit:
    # the full-matrix reduction sits in the timed accumulator's dependency
    # cone (no backend can elide it — the final count is asserted against
    # the exact expected value below), and single-jit fusion means XLA
    # never materializes the [slots, V] matrix between kernels. Calling
    # the unjitted routing_step keeps the whole view one program.
    @jax.jit
    def fused_view(state, batch, acc):
        result = routing_step(state, batch, jnp.int32(0), axis_name=None)
        return result.state, acc + result.deliver.sum(dtype=jnp.int32)

    per_batch_msgs = [int(np.asarray(b.valid).sum()) for b in batches]
    # int32 accumulator wrapping mod 2^32 (x64 is off; modular sums are
    # order-independent, so the exact-count check compares mod 2^32 —
    # same pattern as bench.py)
    M32 = 1 << 32
    acc = jnp.zeros((), jnp.int32)
    state, acc = fused_view(state, batches[0], acc)  # compile + warm
    jax.block_until_ready(acc)
    # host readback before timing: the exact-count baseline the timed
    # loop's final count is asserted against
    warmup_deliveries = int(acc)

    total_msgs = 0
    t0 = time.perf_counter()
    for v in range(args.views):
        state, acc = fused_view(state, batches[v % len(batches)], acc)
        total_msgs += per_batch_msgs[v % len(batches)]
    jax.block_until_ready(acc)
    dt = time.perf_counter() - t0
    # deliveries per view: proposal -> V validators, DA -> committee,
    # votes -> 1 leader each
    per_view_deliveries = V + DA_COMMITTEE + min(V, args.slots - 2)
    # elision-proof: the accumulated on-device count must equal the
    # closed-form expectation for every timed view (+1 for the warmup)
    expected = ((args.views + 1) * per_view_deliveries) % M32
    measured = int(acc) % M32
    if measured != expected:
        raise SystemExit(
            f"delivery-count mismatch: device accumulated {measured}, "
            f"expected {expected} — the timed cone was not fully forced")

    print(json.dumps({
        "bench": "consensus_replay",
        "validators": V,
        "views": args.views,
        "consensus_msgs_per_sec": round(total_msgs / dt, 1),
        "deliveries_per_sec": round(args.views * per_view_deliveries / dt, 1),
        "views_per_sec": round(args.views / dt, 2),
        "per_view_deliveries": per_view_deliveries,
        "device_count_check": "exact",
    }))


if __name__ == "__main__":
    main()
