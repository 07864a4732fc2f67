"""Plain reference of the filters a configuration states.

Exact integer correlation of one 8-bit image with the outer product of the
filter's column and row taps, zero padding at the border ('same' size),
then the fixed-point epilogue: `abs` for derivative filters, a rounding
right shift by `shift`, and a clip to [0, 255]. Written from the
configuration's tap lists alone; it imports nothing of the program, so a
change to the program's multipliers, kernels or filter bank cannot change
what it answers. REFMLM is error-free, so a correct served output equals
this reference byte for byte.
"""
from __future__ import annotations

import numpy as np


def filter_ref(img: np.ndarray, spec: dict) -> np.ndarray:
    """(H, W) uint8 -> (H, W) uint8 under the filter `spec` of a config."""
    taps = np.outer(np.asarray(spec["col"], np.int64),
                    np.asarray(spec["row"], np.int64))
    kh, kw = taps.shape
    h, w = img.shape
    padded = np.pad(np.asarray(img, np.int64),
                    ((kh // 2, kh // 2), (kw // 2, kw // 2)))
    acc = np.zeros((h, w), np.int64)
    for di in range(kh):
        for dj in range(kw):
            if taps[di, dj]:
                acc += taps[di, dj] * padded[di:di + h, dj:dj + w]
    if spec["post"] == "abs":
        acc = np.abs(acc)
    shift = int(spec["shift"])
    if shift > 0:
        acc = (acc + (1 << (shift - 1))) >> shift
    return np.clip(acc, 0, 255).astype(np.uint8)


def kernel_size(spec: dict) -> tuple[int, int]:
    """(kh, kw) of a filter spec: the column and row tap counts."""
    return len(spec["col"]), len(spec["row"])
