"""HBM bytes that any implementation of a transform has to move.

A 2-D DWT reads its image once and writes its coefficients once: the
pyramid holds exactly as many samples as the image, whatever the level
count.  So the least traffic is ``samples * (in_itemsize +
out_itemsize)`` for the forward and for the inverse alike: 8 bytes a
sample for float32 in and out, 6 for 16-bit samples in and float32
coefficients out.  Intermediate levels are not
counted: a fused kernel keeps them on chip, and a per-level count
would let it read above 100 % of the roofline.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def transform_bytes(shape, in_itemsize: int, out_itemsize: int) -> int:
    """Input read once plus output written once."""
    return math.prod(int(n) for n in shape) * (int(in_itemsize)
                                               + int(out_itemsize))


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown
    device is an error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    try:
        return table[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}") from None


def least_seconds(shape, in_itemsize: int, out_itemsize: int,
                  device_kind: str, chips: int = 1) -> float:
    """The least time ``chips`` chips need to move the ideal bytes."""
    return transform_bytes(shape, in_itemsize, out_itemsize) / (
        peaks(device_kind)["hbm_bytes_per_s"] * chips)
