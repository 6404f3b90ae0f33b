"""A subscriber's account of what it received, per (publisher, stream).

The client library's own detector is keyed by (topic, sequence), which
breaks as soon as two publishers share a topic. Here every stream is
known to start at 0, so the first frame seen anchors nothing: a stream
that opens at 5 has five holes. A hole filled later is a reorder (legal
for at-least-once delivery, but the benchmark's configurations promise
FIFO per stream, so ``correct`` fails on it); a sequence seen twice is a
duplicate (legal, reported as a layer metric)."""

from __future__ import annotations

from typing import Dict, List, Set, Tuple


class StreamState:
    __slots__ = ("hi", "unique", "holes", "reorders", "duplicates")

    def __init__(self):
        self.hi = 0            # highest sequence seen + 1
        self.unique = 0
        self.holes: Set[int] = set()
        self.reorders = 0
        self.duplicates = 0

    def observe(self, seq: int) -> bool:
        """Account one arrival; True unless it is a duplicate."""
        hi = self.hi
        if seq == hi:
            self.hi = hi + 1
        elif seq > hi:
            self.holes.update(range(hi, seq))
            self.hi = seq + 1
        elif seq in self.holes:
            self.holes.discard(seq)
            self.reorders += 1
        else:
            self.duplicates += 1
            return False
        self.unique += 1
        return True

    def report(self) -> List[int]:
        return [self.unique, self.hi, len(self.holes), self.reorders,
                self.duplicates]


class GapDetector:
    """All of one subscriber's streams."""

    __slots__ = ("streams",)

    def __init__(self):
        self.streams: Dict[Tuple[int, int], StreamState] = {}

    def observe(self, publisher: int, stream: int, seq: int) -> bool:
        key = (publisher, stream)
        state = self.streams.get(key)
        if state is None:
            state = self.streams[key] = StreamState()
        return state.observe(seq)

    def report(self) -> Dict[str, List[int]]:
        """``"publisher.stream" -> [unique, hi, open, reorders, dups]``."""
        return {f"{p}.{s}": st.report()
                for (p, s), st in self.streams.items()}
