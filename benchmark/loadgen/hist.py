"""A log-bucket histogram of positive integers (nanoseconds): buckets 1 %
wide, so a percentile read from it is within half a per cent of the
sample's. One per client process; the parent merges them by adding the
counts."""

from __future__ import annotations

import math
from typing import List, Optional

_K = 1.0 / math.log(1.01)
BUCKETS = int(math.log(1e12) * _K) + 2  # up to 1,000 s


class LogHistogram:
    __slots__ = ("counts", "n")

    def __init__(self, counts: Optional[List[int]] = None):
        self.counts = list(counts) if counts is not None else [0] * BUCKETS
        self.n = sum(self.counts)

    def add(self, value: int) -> None:
        i = int(math.log(value) * _K) if value > 1 else 0
        self.counts[i if i < BUCKETS else BUCKETS - 1] += 1
        self.n += 1

    def merge(self, other: "LogHistogram") -> None:
        self.counts = [a + b for a, b in zip(self.counts, other.counts)]
        self.n += other.n

    def percentile(self, q: float) -> Optional[float]:
        """The value below which ``q`` per cent of the samples lie, taken
        at the geometric middle of the bucket the rank falls in."""
        if not self.n:
            return None
        rank = q / 100.0 * (self.n - 1)
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if c and seen > rank:
                return math.exp((i + 0.5) / _K)
        return math.exp((BUCKETS - 0.5) / _K)
