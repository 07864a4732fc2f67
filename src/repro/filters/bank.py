"""The filter bank: named integer-coefficient 2-D image filters for the
REFMLM datapath (DESIGN.md §5).

The paper evaluates its multiplier inside exactly one filter -- a 3x3
Gaussian (§3.3, Fig. 9) -- but the datapath it builds (8-bit pixel x 8-bit
coefficient products into a CSA accumulator, shift-normalize, clip) is the
generic FPGA convolution engine of "High Throughput 2D Spatial Image Filters
on FPGAs" (arXiv:1710.05154). This module generalizes the coefficient side:
each `FilterSpec` is a KxK integer tap table plus the fixed-point bookkeeping
(`shift`, `post`) the engine needs, and -- where the kernel is rank-1 -- the
separable row/column decomposition whose two 1-D passes halve the tap
products per pixel (the TPU analogue of the paper's line-buffer reuse).

Fixed-point convention (paper Fig. 9): smoothing-filter coefficients are
scaled so the tap table sums to ~2**shift; the engine computes
`(acc + 2**(shift-1)) >> shift` so unit-gain filters stay unit-gain in
integer arithmetic. Derivative filters (Sobel, Laplacian) use shift=0 and
`post='abs'` (gradient magnitude display convention).

Separability contract: for a separable spec the 2-D table IS the outer
product of the integer row/column vectors -- not an independently rounded
2-D sampling -- so with an exact multiplier ('exact', 'refmlm') the two-pass
path is bit-identical to the direct path (asserted in tests).

All coefficient magnitudes fit 8 bits, matching the paper's 8x8 REFMLM. The
separable second pass is sized from the row pass's worst intermediate
(`max_intermediate`), which follows the top of the pixels' container: 8-bit
pixels give up to 16-bit intermediates and the 16x16 recursion, 16-bit
pixels up to 21-bit ones and the 32x32 recursion (DESIGN.md §5).
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class FilterSpec(NamedTuple):
    """One filter of the bank, in the integer datapath's terms."""

    name: str
    taps: np.ndarray            # (kh, kw) int32 coefficient table
    shift: int                  # output normalization: acc >> shift
    post: str                   # 'clip' (smoothing) | 'abs' (derivative)
    sep_row: np.ndarray | None  # (kw,) int32 horizontal pass, or None
    sep_col: np.ndarray | None  # (kh,) int32 vertical pass, or None

    @property
    def separable(self) -> bool:
        return self.sep_row is not None

    @property
    def ksize(self) -> tuple[int, int]:
        return self.taps.shape  # type: ignore[return-value]


def gaussian_kernel_1d(ktaps: int, sigma: float, scale: int) -> np.ndarray:
    """Sampled, truncated 1-D Gaussian rounded to integers summing to `scale`.

    The center tap absorbs the rounding residue so that the outer-product 2-D
    table sums to exactly scale**2 (unit gain after the shift).
    """
    assert ktaps % 2 == 1
    r = ktaps // 2
    xs = np.arange(-r, r + 1, dtype=np.float64)
    g = np.exp(-(xs**2) / (2.0 * sigma**2))
    k = np.round(g / g.sum() * scale).astype(np.int64)
    k[r] += scale - k.sum()
    assert k.sum() == scale and (k > 0).all()
    return k.astype(np.int32)


def _separable(name: str, row: np.ndarray, col: np.ndarray, shift: int,
               post: str = "clip") -> FilterSpec:
    taps = np.outer(col.astype(np.int64), row.astype(np.int64)).astype(np.int32)
    return FilterSpec(name, taps, shift, post,
                      row.astype(np.int32), col.astype(np.int32))


def _direct(name: str, taps: list[list[int]], shift: int,
            post: str = "clip") -> FilterSpec:
    return FilterSpec(name, np.asarray(taps, np.int32), shift, post, None, None)


def _build_bank(sigma: float = 1.0) -> dict[str, FilterSpec]:
    g3 = gaussian_kernel_1d(3, sigma, scale=16)          # [4, 8, 4]
    g5 = gaussian_kernel_1d(5, sigma, scale=16)          # [1, 4, 6, 4, 1]
    return {
        # Smoothing family: unit gain, shift-8 normalization (paper Fig. 9).
        "gaussian3": _separable("gaussian3", g3, g3, shift=8),
        "gaussian5": _separable("gaussian5", g5, g5, shift=8),
        # 4 * 7 = 28 ~ 256/9: the closest unit-gain rank-1 box at shift 8.
        "box3": _separable("box3", np.full(3, 4, np.int64),
                           np.full(3, 7, np.int64), shift=8),
        # Sharpen: 32 * (identity + laplacian), shift 5.
        "sharpen3": _direct("sharpen3", [[0, -32, 0],
                                         [-32, 160, -32],
                                         [0, -32, 0]], shift=5),
        # Derivative family: shift 0, |.| display convention.
        "sobel_x": _separable("sobel_x", np.array([-1, 0, 1], np.int64),
                              np.array([1, 2, 1], np.int64), shift=0, post="abs"),
        "sobel_y": _separable("sobel_y", np.array([1, 2, 1], np.int64),
                              np.array([-1, 0, 1], np.int64), shift=0, post="abs"),
        "laplacian": _direct("laplacian", [[0, 1, 0],
                                           [1, -4, 1],
                                           [0, 1, 0]], shift=0, post="abs"),
    }


FILTER_BANK: dict[str, FilterSpec] = _build_bank()
FILTER_NAMES: tuple[str, ...] = tuple(FILTER_BANK)


def get_filter(name: str, *, sigma: float | None = None) -> FilterSpec:
    """Look up a bank filter; `sigma` re-samples the Gaussian members."""
    if sigma is not None and name in ("gaussian3", "gaussian5"):
        return _build_bank(sigma)[name]
    try:
        return FILTER_BANK[name]
    except KeyError:
        raise ValueError(
            f"unknown filter {name!r}; bank: {FILTER_NAMES}") from None


def max_intermediate(spec: FilterSpec, pixel_max: int = 255) -> int:
    """Worst-case |row-pass accumulator| -- sizes the second-pass multiplier.
    `pixel_max` is the top of the pixels' container (255, or 65535 for
    16-bit containers)."""
    if not spec.separable:
        return 0
    return int(pixel_max * np.abs(spec.sep_row.astype(np.int64)).sum())
