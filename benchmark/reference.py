"""The plain reference: a dict-and-set router in pure Python.

It shares nothing with the program — not ``Connections``, not a kernel,
not a message type. Given who subscribes to what and what was published,
it says how many frames of each (publisher, stream) every user is owed:
a broadcast goes to every user subscribed to its topic at publish time
(the sender included, as upstream does), a direct or a probe to its
recipient and to nobody else. Streams count from 0 without gaps, so a
count of n owes exactly the sequences 0..n-1.
"""

from __future__ import annotations

from collections import Counter
from typing import Dict, Iterable, List, Sequence, Set, Tuple

from benchmark.loadgen.plan import BROADCAST, STREAM_DIRECT, STREAM_PROBE, DIRECT

Owed = List[Dict[Tuple[int, int], int]]  # per user: (publisher, stream) -> n


def route(subscriptions: Sequence[Set[int]],
          log: Iterable[Tuple[int, int, int]]) -> Owed:
    """``log`` holds one ``(publisher, kind, target)`` per published frame."""
    subscribers: Dict[int, List[int]] = {}
    for user, topics in enumerate(subscriptions):
        for topic in topics:
            subscribers.setdefault(topic, []).append(user)
    owed: Owed = [{} for _ in subscriptions]
    for (publisher, kind, target), n in Counter(log).items():
        if kind == BROADCAST:
            for user in subscribers.get(target, ()):
                key = (publisher, target)
                owed[user][key] = owed[user].get(key, 0) + n
        else:
            key = (publisher, STREAM_DIRECT if kind == DIRECT else STREAM_PROBE)
            owed[target][key] = owed[target].get(key, 0) + n
    return owed


def total(owed: Owed) -> int:
    return sum(n for user in owed for n in user.values())


def compare(owed: Owed, reports: Sequence[Dict[str, List[int]]]) -> List[str]:
    """Hold each user's report (``gaps.GapDetector.report``) to what it is
    owed; returns the differences as text, none when they agree."""
    problems: List[str] = []
    for user, (want, got) in enumerate(zip(owed, reports)):
        seen = {}
        for name, row in got.items():
            publisher, stream = name.split(".")
            seen[int(publisher), int(stream)] = row
        for key in sorted(set(want) | set(seen)):
            n = want.get(key, 0)
            unique, hi, holes, reorders, _dups = seen.get(key, (0, 0, 0, 0, 0))
            if unique != n or hi != n or holes or reorders:
                problems.append(
                    f"user {user} stream {key}: owed {n}, got {unique} unique "
                    f"up to {hi}, {holes} missing, {reorders} reordered")
    return problems
