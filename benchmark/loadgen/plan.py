"""The traffic generator's arithmetic: who the users are, what each one
subscribes to, and what the k-th frame of publisher p is. Everything
here is a pure function of (configuration, traffic file, seed), so the
client processes, the parent and the plain reference all derive the same
plan without exchanging it. No program code is imported.

Payload layout (the benchmark owns it; the program sees opaque bytes):

    publisher u16 | stream u16 | seq u32 | due_ns u64 | target u32 | filler

``stream`` is the topic for a broadcast, ``STREAM_DIRECT`` for a direct
and ``STREAM_PROBE`` for a flow-control probe; ``seq`` counts from 0 per
(publisher, topic) for broadcasts and per (publisher, recipient) for
directs and probes, so every subscriber sees each of its streams as
0, 1, 2, ... ``target`` repeats the topic, or names the recipient's user
index (a direct that reaches anyone else is caught by it). The filler is
a slice of a pool drawn from the seed, at an offset the receiver can
recompute, so a sampled payload is compared byte for byte.
"""

from __future__ import annotations

import bisect
import itertools
import math
import random
import struct
from typing import Iterator, List, NamedTuple, Sequence, Set

HEADER = struct.Struct("<HHIQI")
HEADER_BYTES = HEADER.size  # 20
STREAM_DIRECT = 0xFFFF
STREAM_PROBE = 0xFFFE
BROADCAST, DIRECT, PROBE = 0, 1, 2
POOL_BYTES = 1 << 16
MAX_PAYLOAD_BYTES = 1 << 15
# The harness's own warm-up flow, the same in every cell: one user (the
# last) sends itself small directs before the cell's flows start, so that
# the step programs of the cell's user bucket are loaded before the window
# whatever the cell's traffic does later (loadgen/pack.py, ``_prelude``).
# A traffic file cannot name this loop kind.
PRELUDE_BURSTS = (16, 4)  # over the latency slice's 8 frames, then under
PRELUDE_FLOW = {
    "name": "prelude", "publishers": 1, "loop": {"kind": "prelude"},
    "mix": [{"share": 1.0, "kind": "direct", "bytes": 64, "to": "self"}]}


class Frame(NamedTuple):
    kind: int    # BROADCAST | DIRECT | PROBE
    target: int  # topic (broadcast) or recipient user index
    nbytes: int  # whole payload, header included


def stream_of(frame: Frame) -> int:
    if frame.kind == BROADCAST:
        return frame.target
    return STREAM_DIRECT if frame.kind == DIRECT else STREAM_PROBE


def key_seed(seed: int, user: int) -> int:
    """Seed of user ``user``'s keypair (fits the scheme's u64)."""
    return ((seed & 0xFFFFFFFF) << 24) + user + 1


def make_pool(seed: int) -> bytes:
    return random.Random(f"{seed}:pool").randbytes(POOL_BYTES)


def filler_offset(publisher: int, stream: int, seq: int, nbytes: int) -> int:
    return (seq * 40503 + publisher * 9973 + stream) % (POOL_BYTES - nbytes)


def build_payload(pool: bytes, publisher: int, frame: Frame, seq: int,
                  due_ns: int) -> bytes:
    stream = stream_of(frame)
    fill = frame.nbytes - HEADER_BYTES
    off = filler_offset(publisher, stream, seq, fill)
    return HEADER.pack(publisher, stream, seq, due_ns, frame.target) \
        + pool[off:off + fill]


class Layout:
    """Which user index publishes, which placement group and which client
    process each user belongs to. The cell's publisher k of n is user
    ``k * users // n`` (spread evenly, so evenly over the placement
    groups too); the last publisher is the harness's own, the last user,
    with ``PRELUDE_FLOW`` as its flow. Group of user u is
    ``u * groups // users``. Subscriber-only users fill ``sub_procs``
    processes in contiguous ranges; publishers live in ``pub_procs``
    processes of their own, so that a publisher's clock is not at the
    mercy of its neighbours' fan-in."""

    def __init__(self, users: int, groups: int, sub_procs: int,
                 pub_procs: int, flows: Sequence[dict]):
        self.users, self.groups = users, groups
        self.sub_procs, self.pub_procs = sub_procs, pub_procs
        self.flow_of_pub: List[dict] = [
            flow for flow in (*flows, PRELUDE_FLOW)
            for _ in range(flow["publishers"])]
        self.publishers = len(self.flow_of_pub)
        own = self.publishers - 1  # the cell's own publishers
        if not 0 < own < users:
            raise ValueError(f"{own} publishers for {users} users")
        self.pub_users = [k * users // own for k in range(own)] + [users - 1]
        self.pub_of_user = {u: k for k, u in enumerate(self.pub_users)}

    @property
    def procs(self) -> int:
        return self.sub_procs + self.pub_procs

    def group_of(self, user: int) -> int:
        return user * self.groups // self.users

    def group_users(self, group: int) -> range:
        lo = -(-group * self.users // self.groups)
        hi = -(-(group + 1) * self.users // self.groups)
        return range(lo, hi)

    def users_of_proc(self, proc: int) -> List[int]:
        if proc < self.sub_procs:
            lo = proc * self.users // self.sub_procs
            hi = (proc + 1) * self.users // self.sub_procs
            return [u for u in range(lo, hi) if u not in self.pub_of_user]
        j = proc - self.sub_procs
        return [u for k, u in enumerate(self.pub_users)
                if k % self.pub_procs == j]


def subscriptions(rules: Sequence[dict], users: int) -> List[Set[int]]:
    """Each user's topic set: the union of the rules that cover it. A rule
    is ``{"users": "all" | [lo, hi), "topic": {"fixed": t} | {"mod": m}}``."""
    table: List[Set[int]] = [set() for _ in range(users)]
    for rule in rules:
        span = rule["users"]
        lo, hi = (0, users) if span == "all" else (span[0], min(span[1], users))
        topic = rule["topic"]
        for u in range(lo, hi):
            table[u].add(topic["fixed"] if "fixed" in topic
                         else u % topic["mod"])
    return table


def zipf_edges(n: int, s: float) -> List[float]:
    """Cumulative weights of a Zipf draw over topics ``0..n-1``: topic
    ``k`` has weight ``(k + 1) ** -s``."""
    return list(itertools.accumulate((k + 1) ** -s for k in range(n)))


def mix_block(shares: Sequence[float]) -> List[int]:
    """The mix entries, by index, of one block of an open loop's frames:
    the smallest block of at most 100 frames that holds every entry at
    its share exactly (0.9 and 0.1: nine and one), else 100 frames shared
    out by largest remainder."""
    parts = [share / sum(shares) for share in shares]
    size = next((n for n in range(1, 100) if all(
        abs(part * n - round(part * n)) < 1e-9 for part in parts)), 100)
    exact = [part * size for part in parts]
    counts = [int(x + 1e-9) for x in exact]
    for i in sorted(range(len(exact)),
                    key=lambda i: counts[i] - exact[i])[:size - sum(counts)]:
        counts[i] += 1
    return [i for i, count in enumerate(counts) for _ in range(count)]


def frame_plan(seed: int, layout: Layout, flow: dict,
               publisher: int) -> Iterator[Frame]:
    """Publisher ``publisher``'s frames in the order it sends them. In a
    windowed loop every ``probe_every``-th frame is a probe to itself and
    draws nothing, so the other frames do not depend on the spacing. In an
    open loop, which sends a number of frames fixed by its rate, the mix
    entries come block by block (``mix_block``), each block in an order
    drawn from the seed, so that every seed offers the same sizes and
    fan-outs in another order; in the other loops each frame draws its
    entry. A broadcast's topic is ``fixed`` (no draw), ``uniform`` over
    ``n`` or ``zipf`` over ``n`` with exponent ``s`` (one draw each)."""
    rng = random.Random(f"{seed}:{publisher}:frames")
    me = layout.pub_users[publisher]
    loop = flow["loop"]
    every = loop["probe_every"] if loop["kind"] == "windowed" else 0
    mix = flow["mix"]
    edges, acc = [], 0.0
    for entry in mix:
        acc += entry["share"]
        edges.append(acc)
    zipf = {i: zipf_edges(entry["topic"]["zipf"], entry["topic"].get("s", 1.0))
            for i, entry in enumerate(mix)
            if "zipf" in entry.get("topic", ())}
    block, left = None, []
    if loop["kind"] == "open":
        block = mix_block([entry["share"] for entry in mix])
        order = random.Random(f"{seed}:{publisher}:mix")
    k = 0
    while True:
        k += 1
        if every and k % every == 0:
            yield Frame(PROBE, me, loop["probe_bytes"])
            continue
        if block is None:
            i = min(bisect.bisect_right(edges, rng.random() * acc),
                    len(mix) - 1)
        else:
            if not left:
                left = block[:]
                order.shuffle(left)
            i = left.pop()
        entry = mix[i]
        if entry["kind"] == "broadcast":
            topic = entry["topic"]
            if "fixed" in topic:
                target = topic["fixed"]
            elif i in zipf:
                target = min(bisect.bisect_right(
                    zipf[i], rng.random() * zipf[i][-1]), len(zipf[i]) - 1)
            else:
                target = rng.randrange(topic["uniform"])
            yield Frame(BROADCAST, target, entry["bytes"])
        else:
            to = entry["to"]
            if to == "self":
                target = me
            else:
                group = (layout.group_of(me) + to["group_offset"]) \
                    % layout.groups
                span = layout.group_users(group)
                target = span[rng.randrange(len(span))]
            yield Frame(DIRECT, target, entry["bytes"])


def arrivals(seed: int, publisher: int, rate_per_s: float, span_ns: int,
             part: str) -> List[int]:
    """When one publisher's open-loop frames are due inside a span (``part``
    names it: the warm-up's, the window's), in nanoseconds from its start,
    ascending: ``round(rate × span)`` of them whatever the seed, so that
    every seed offers the same work. The gaps are those of a Poisson
    process laid out evenly over their distribution (the n + 1
    mid-quantiles of the exponential), scaled to fill the span, in an
    order drawn from the seed; the last gap runs to the span's end."""
    n = round(rate_per_s * span_ns / 1e9)
    gaps = [-math.log(1 - (i + 0.5) / (n + 1)) for i in range(n + 1)]
    scale = span_ns / sum(gaps)  # before the shuffle: the same for every seed
    random.Random(f"{seed}:{publisher}:arrivals:{part}").shuffle(gaps)
    return [int(t * scale) for t in itertools.accumulate(gaps[:n])]
