"""Port blocks for harnesses that lay several listeners out from one base
port (``two_host.make_two_host_node``: marshal at base+1+rank, brokers at
base+10+10*rank and +1).

A base taken from ``bind(("127.0.0.1", 0))`` reserves nothing beyond
itself, and Linux hands ``bind(0)`` odd ports and ``connect()`` even
ones: base+1, +11 and +21 sit exactly where every other process's next
outgoing connection lands, and a closed client leaves its port in
TIME_WAIT (which refuses a listener) for a minute. A block from below
the kernel's ephemeral range cannot be taken that way at all.
"""

from __future__ import annotations

import random
import socket

SPAN = 32


def _ephemeral_low() -> int:
    try:
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            return int(f.read().split()[0])
    except (OSError, ValueError, IndexError):
        return 32768


def free_port_block() -> int:
    """A base port with ``SPAN`` ports from it free right now, below the
    ephemeral range (so only another caller of this function competes
    for them, and the draw is random)."""
    draw = random.SystemRandom()
    top = min(_ephemeral_low(), 32768) - SPAN
    for _ in range(256):
        base = draw.randrange(10240, top, SPAN)
        held = []
        try:
            for port in range(base, base + SPAN):
                s = socket.socket()
                held.append(s)
                s.bind(("127.0.0.1", port))
            return base
        except OSError:
            continue
        finally:
            for s in held:
                s.close()
    raise OSError("no free block of %d ports below the ephemeral range"
                  % SPAN)
