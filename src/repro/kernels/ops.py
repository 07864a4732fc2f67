"""jit'd public wrappers around the Pallas kernels: float in, float out.

These handle quantization / limb decomposition / padding outside the kernels
so kernel bodies stay pure-integer (like the paper's RTL) and bit-exact
against ref.py.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import Array

from repro.core.quant import quantize_limbs, quantize_magnitude
from repro.filters.pipeline import apply_filter, filter_bank_apply
from repro.kernels.gaussian_conv import gaussian_conv3x3_kernel, gaussian_kernel_3x3
from repro.kernels.karatsuba_matmul import karatsuba_matmul_kernel
from repro.kernels.mitchell_matmul import mitchell_matmul_kernel


def _pad_to(x: Array, mult0: int, mult1: int) -> Array:
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    return jnp.pad(x, ((0, p0), (0, p1))) if (p0 or p1) else x


@partial(jax.jit, static_argnames=("num_ecc", "case_split", "nbits", "block_m",
                                   "block_n", "block_k", "accum", "interpret"))
def lns_matmul(
    a: Array,
    b: Array,
    *,
    nbits: int = 8,
    num_ecc: int = 0,
    case_split: bool = True,
    block_m: int = 16,
    block_n: int = 128,
    block_k: int = 128,
    accum: str = "scratch",
    interpret: bool | None = None,
) -> Array:
    """Approximate float matmul via the Mitchell-family Pallas kernel.

    a (M, K) x b (K, N) -> f32 (M, N). num_ecc=0/case_split=True is Mitchell's
    algorithm; case_split=False with k ECCs is the Babic iterative multiplier.
    `accum` picks the K-reduction carry (VMEM scratch vs in-place output,
    DESIGN.md §8) -- bit-identical, benchmark axis only.
    """
    qa = quantize_magnitude(a, nbits)
    qb = quantize_magnitude(b, nbits)
    sa = _pad_to(qa.magnitude * qa.sign, block_m, block_k)
    sb = _pad_to(qb.magnitude * qb.sign, block_k, block_n)
    acc = mitchell_matmul_kernel(
        sa, sb, num_ecc=num_ecc, case_split=case_split,
        block_m=block_m, block_n=block_n, block_k=block_k, accum=accum,
        interpret=interpret,
    )[: a.shape[0], : b.shape[1]]
    return acc.astype(jnp.float32) * (qa.scale * qb.scale)


@partial(jax.jit, static_argnames=("karatsuba", "block_m", "block_n", "block_k",
                                   "accum", "interpret"))
def limb_matmul(
    a: Array,
    b: Array,
    *,
    karatsuba: bool = True,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    accum: str = "scratch",
    interpret: bool | None = None,
) -> Array:
    """Exact wide-int matmul from 3 (karatsuba) or 4 (schoolbook) int8 passes.

    `accum` picks the K-reduction carry (VMEM scratch vs in-place output,
    DESIGN.md §8) -- bit-identical, benchmark axis only.
    """
    da, sa = quantize_limbs(a, karatsuba=karatsuba)
    db, sb = quantize_limbs(b, karatsuba=karatsuba)
    w = da.limb_bits
    ah = _pad_to(da.hi, block_m, block_k)
    al = _pad_to(da.lo, block_m, block_k)
    bh = _pad_to(db.hi, block_k, block_n)
    bl = _pad_to(db.lo, block_k, block_n)
    hh, mid, ll = karatsuba_matmul_kernel(
        ah, al, bh, bl, karatsuba=karatsuba,
        block_m=block_m, block_n=block_n, block_k=block_k, accum=accum,
        interpret=interpret,
    )
    m, n = a.shape[0], b.shape[1]
    acc = (hh[:m, :n].astype(jnp.float32) * float(1 << (2 * w))
           + mid[:m, :n].astype(jnp.float32) * float(1 << w)
           + ll[:m, :n].astype(jnp.float32))
    return acc * (sa * sb)


def gaussian_filter(
    img: Array,
    kernel: Array,
    *,
    method: str = "refmlm",
    nbits: int = 8,
    block_rows: int | None = None,
    interpret: bool | None = None,
) -> Array:
    """3x3 Gaussian smoothing of a uint8 image with the selected multiplier.

    Legacy entry point (paper Fig. 9 2-D-sampled table). The general batched
    filter bank -- Gaussian 3x3/5x5, box, sharpen, Sobel, Laplacian, direct
    or separable -- is `apply_filter` / `filter_bank_apply` from
    repro.filters (re-exported here; DESIGN.md §5).

    Deliberately NOT wrapped in an outer `jax.jit`: tracing would turn the
    coefficient table into a Tracer, which rides in SMEM instead of being
    baked into the kernel (DESIGN.md §7). The conv pass jits internally;
    a caller's own jit still composes (on the SMEM path).
    """
    out = gaussian_conv3x3_kernel(
        img.astype(jnp.int32), kernel, method=method, nbits=nbits,
        block_rows=block_rows, interpret=interpret,
    )
    return out.astype(jnp.uint8)


__all__ = ["lns_matmul", "limb_matmul", "gaussian_filter", "gaussian_kernel_3x3",
           "apply_filter", "filter_bank_apply"]
