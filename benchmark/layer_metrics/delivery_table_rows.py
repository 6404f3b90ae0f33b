"""Rows of the user table the device decided for: the largest ``users``
dimension among the delivery kernel's calls in the traced span, read off
each call's HLO text like the roofline's shapes (the result is ``[users,
frames]``, the first operand the ``u32[users, mask_words]`` table). With
``staged_share`` near 1 and no unmirrored user it says the device took
the decision for the whole committee. Nothing to read (a CPU run, a
program without the kernel) leaves the metric out."""

import re

LAYER = "routing_step"
UNIT = "users"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "delivery_p50_ms"

CALL = re.compile(r"= \w+\[(\d+),(\d+)\]\S* custom-call\(u32\[(\d+),(\d+)\]")


def read(run):
    t = run.window.trace
    name = run.config.get("kernels", {}).get("delivery")
    row = t["kernels"].get(name) if t and name else None
    calls = map(CALL.search, row["calls"] if row else ())
    return max((int(m.group(1)) for m in calls
                if m and m.group(1) == m.group(3)), default=None)
