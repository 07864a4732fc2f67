"""Pure-jnp oracles for the filter subsystem -- independently-written
shift-and-accumulate loops (not the kernel's helper), so tests compare two
implementations of the same dataflow (DESIGN.md §5).

Bit-exact contract: integer in, integer out, same accumulator dtype and
same fixed-point epilogue as the Pallas path.

`plain_filter_ref` is the plainest of them: int64 NumPy correlation with
the filter's 2-D taps, the rounding shift, and abs/clip to the top of the
pixels' container. It uses no multiplier, kernel or dataflow of the
program, so it holds every exact path to the same answer (DESIGN.md §5).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.filters.bank import FilterSpec, get_filter, max_intermediate
from repro.filters.conv import second_pass_nbits, tap_multiplier


def conv2d_ref(
    imgs: Array,
    taps: Array | np.ndarray,
    *,
    method: str = "refmlm",
    nbits: int = 8,
    shift: int = 8,
    post: str = "clip",
) -> Array:
    """(N, H, W) int32 batched convolution oracle, signed-magnitude taps."""
    taps = jnp.asarray(taps, jnp.int32)
    kh, kw = taps.shape
    n, h, w = imgs.shape
    padded = jnp.pad(imgs.astype(jnp.int32),
                     ((0, 0), (kh // 2, kh // 2), (kw // 2, kw // 2)))
    mult = tap_multiplier(method)
    acc = jnp.zeros((n, h, w), jnp.int32)
    for di in range(kh):
        for dj in range(kw):
            tap = padded[:, di : di + h, dj : dj + w]
            c = taps[di, dj]
            prod = mult(jnp.abs(tap),
                        jnp.broadcast_to(jnp.abs(c), tap.shape), nbits)
            acc = acc + jnp.sign(c) * jnp.sign(tap) * prod
    if post == "none":
        return acc
    if post == "abs":
        acc = jnp.abs(acc)
    out = (acc + (1 << (shift - 1))) >> shift if shift > 0 else acc
    return jnp.clip(out, 0, 255)


def apply_filter_ref(
    imgs: Array,
    filt: FilterSpec | str,
    *,
    method: str = "refmlm",
    nbits: int = 8,
    separable: bool | None = None,
) -> Array:
    """Oracle for pipeline.apply_filter on an (N, H, W) batch -> uint8."""
    spec = get_filter(filt) if isinstance(filt, str) else filt
    if separable is None:
        separable = spec.separable
    if separable:
        row = np.asarray(spec.sep_row, np.int64)[None, :]
        col = np.asarray(spec.sep_col, np.int64)[:, None]
        nb2 = second_pass_nbits(max_intermediate(spec),
                                int(np.abs(spec.sep_col).max()))
        tmp = conv2d_ref(imgs, row, method=method, nbits=nbits,
                         shift=0, post="none")
        out = conv2d_ref(tmp, col, method=method, nbits=nb2,
                         shift=spec.shift, post=spec.post)
    else:
        out = conv2d_ref(imgs, spec.taps, method=method, nbits=nbits,
                         shift=spec.shift, post=spec.post)
    return out.astype(jnp.uint8)


def plain_filter_ref(imgs, filt: FilterSpec | str) -> np.ndarray:
    """Exact filtering of an (H, W) image or (N, H, W) batch, in int64
    NumPy: 'same'-size correlation with the filter's 2-D taps over zero
    padding, then `abs` for derivative filters, the rounding shift, and
    the clip to the top of the pixels' container -- uint16 for uint16
    images, else uint8 -- returned in that dtype."""
    spec = get_filter(filt) if isinstance(filt, str) else filt
    arr = np.asarray(imgs)
    dtype = np.dtype(np.uint16 if arr.dtype == np.uint16 else np.uint8)
    x = arr.astype(np.int64).reshape((-1,) + arr.shape[-2:])
    taps = np.asarray(spec.taps, np.int64)
    kh, kw = taps.shape
    n, h, w = x.shape
    padded = np.pad(x, ((0, 0), (kh // 2, kh // 2), (kw // 2, kw // 2)))
    acc = np.zeros((n, h, w), np.int64)
    for di in range(kh):
        for dj in range(kw):
            if taps[di, dj]:
                acc += taps[di, dj] * padded[:, di:di + h, dj:dj + w]
    if spec.post == "abs":
        acc = np.abs(acc)
    if spec.shift > 0:
        acc = (acc + (1 << (spec.shift - 1))) >> spec.shift
    out = np.clip(acc, 0, np.iinfo(dtype).max).astype(dtype)
    return out.reshape(arr.shape)


__all__ = ["apply_filter_ref", "conv2d_ref", "plain_filter_ref"]
