"""3x3 Gaussian convolution with a selectable multiplier (paper §3.3) --
now a thin shim over the general filter subsystem in `repro.filters`.

Historically this module held a dedicated single-image Pallas kernel; the
batched, multi-filter generalization lives in `repro/filters/conv.py`
(DESIGN.md §5) and this wrapper keeps the original public surface:

  * `gaussian_kernel_3x3`      -- the paper's Fig. 9 scale-256 tap table
                                  (2-D-sampled; the bank's `gaussian3` uses
                                  the separable outer-product table instead);
  * `gaussian_conv3x3_kernel`  -- single-image (H, W) int32 conv, bit-exact
                                  to the original kernel's dataflow;
  * `_tap_multiplier`          -- the method -> elementwise-product mapping,
                                  re-exported for the oracle in ref.py.
"""
from __future__ import annotations

import numpy as np
from jax import Array

from repro.filters.conv import conv2d_pass, tap_multiplier

_tap_multiplier = tap_multiplier


def gaussian_kernel_3x3(sigma: float = 1.0, scale: int = 256) -> np.ndarray:
    """Sampled, truncated, integer-scaled 2-D Gaussian (paper eq. 25/Fig. 9)."""
    xs = np.arange(-1, 2, dtype=np.float64)
    g = np.exp(-(xs[:, None] ** 2 + xs[None, :] ** 2) / (2.0 * sigma**2))
    g /= 2.0 * np.pi * sigma**2
    k = np.round(g / g.sum() * scale).astype(np.int32)
    return k


def gaussian_conv3x3_kernel(
    img: Array,
    kernel: Array,
    *,
    method: str = "refmlm",
    nbits: int = 8,
    block_rows: int | None = None,
    interpret: bool | None = None,
) -> Array:
    """img (H, W) int32 pixels in [0,255]; kernel (3,3) int32 scale-256.

    block_rows=None defaults through the autotune cache (DESIGN.md §8);
    a concrete (non-traced) `kernel` is baked into the compiled kernel, a
    traced one rides in SMEM (DESIGN.md §7).
    """
    return conv2d_pass(
        img[None], kernel, method=method, nbits=nbits, shift=8, post="clip",
        block_rows=block_rows, interpret=interpret,
    )[0]
