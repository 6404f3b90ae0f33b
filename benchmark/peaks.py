"""The yardstick's constants: published peaks per chip, keyed by the
``device_kind`` JAX reports, and the least bytes the routing step's
delivery kernel must move at its shapes. A device that is not in the
table is an error, never a default."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(f"no published {what} for device kind "
                       f"{device_kind!r}; add it to benchmark/peaks.py with "
                       "its source") from None


def delivery_min_bytes(users: int, frames: int, mask_words: int,
                       out_itemsize: int = 1) -> int:
    """Bytes the delivery decision for one lane cannot avoid moving
    through HBM: read every user's topic mask and ownership word, read
    every frame's topic mask, kind and destination, write the
    ``[users, frames]`` decision (a bool is one byte). The operation is a
    handful of integer ANDs per output byte, so memory bounds it."""
    table = users * (4 * mask_words + 4)
    batch = frames * (4 * mask_words + 4 + 4)
    return table + batch + users * frames * out_itemsize
