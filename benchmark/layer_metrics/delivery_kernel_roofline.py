"""The dense delivery kernel's share of its memory roofline: the least
bytes it must move at its shapes (benchmark/peaks.py) at the chip's peak
HBM bandwidth, over the kernel's own time in the trace. The shapes are
read off each call's HLO text in the trace: the result is ``[users,
frames]`` and the first operand the ``u32[users, mask_words]`` table."""

import re

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "delivery_p50_ms"

CALL = re.compile(r"= \w+\[(\d+),(\d+)\]\S* custom-call\(u32\[(\d+),(\d+)\]")


def read(run):
    from benchmark import peaks
    t = run.window.trace
    name = run.config.get("kernels", {}).get("delivery")
    row = t["kernels"].get(name) if t and name else None
    if not row or not row["count"]:
        return None
    least = 0
    for hlo, (count, _seconds) in row["calls"].items():
        m = CALL.search(hlo)
        if not m or m.group(1) != m.group(3):
            raise ValueError(f"cannot read {name}'s shapes off {hlo[:200]!r}")
        users, frames, words = (int(m.group(i)) for i in (1, 2, 4))
        least += count * peaks.delivery_min_bytes(users, frames, words)
    floor_s = least / peaks.peak(run.device["kind"], "hbm_bytes_per_s")
    return 100.0 * floor_s / row["seconds"]
