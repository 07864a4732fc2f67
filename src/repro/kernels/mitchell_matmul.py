"""Pallas TPU kernel: LNS (Mitchell-family) approximate matmul.

The paper's multiplier datapath (LOD -> mantissa add -> antilog shift ->
k cascaded error-correction circuits) evaluated SIMD-wide on the VPU over
VMEM-resident blocks. The MXU is deliberately NOT used: the whole point of
the paper's multiplier is a multiplication-free datapath, which on TPU maps
to vector shifts/adds.

Tiling: grid (M/bm, N/bn, K/bk); A block (bm, bk) and B block (bk, bn) live
in VMEM; the (bm, bk, bn) broadcast product is the dominant VMEM term
(bm*bk*bn*4 bytes -- default 16x128x128 = 1 MiB). Accumulation is int32
(exact; products < 2^(2*nbits), nbits <= 10), so the kernel is bit-identical
to the pure-jnp oracle in ref.py.

Grid semantics (DESIGN.md §8): the M and N axes are declared `parallel`
(independent output tiles, distributable across megacores); K is the
carried reduction and stays `arbitrary`. The partial sums accumulate in a
VMEM scratch tile -- zero-initialized at k==0, flushed to the output block
at the last k step (`accum='scratch'`, the default) -- so the output ref is
written once instead of read-modify-written every K step;
`accum='output'` keeps the legacy in-place accumulation as the benchmark
baseline. Both orderings produce bit-identical int32 sums.

Inputs are pre-quantized signed integer magnitudes (see ops.py); the kernel
is pure integer arithmetic, like the paper's RTL.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.platform import grid_compiler_params, resolve_interpret

ACCUM_MODES = ("scratch", "output")


def _clz_k(x: Array) -> Array:
    """Leading-one position (paper's LOD), branch-free, on int32 lanes."""
    k = jnp.zeros_like(x)
    for shift in (16, 8, 4, 2, 1):
        gt = x >= (1 << shift)
        k = k + jnp.where(gt, shift, 0)
        x = jnp.where(gt, x >> shift, x)
    return k


def _mantissa_pair(v: Array) -> tuple[Array, Array]:
    k = _clz_k(v)
    return k, v - jnp.where(v > 0, jnp.int32(1) << k, 0)


def _signed_block_product(a: Array, b: Array, *, num_ecc: int, case_split: bool) -> Array:
    """(bm, bk) x (bk, bn) -> (bm, bn) int32 via the Mitchell family.

    num_ecc=0, case_split=True  -> Mitchell's algorithm (MA).
    num_ecc=k, case_split=False -> Babic BB + k ECC stages.
    """
    am = jnp.abs(a)[:, :, None]            # (bm, bk, 1)
    bm_ = jnp.abs(b)[None, :, :]           # (1, bk, bn)
    sgn = (jnp.sign(a)[:, :, None] * jnp.sign(b)[None, :, :]).astype(jnp.int32)

    ra = jnp.broadcast_to(am, (a.shape[0], a.shape[1], b.shape[1]))
    rb = jnp.broadcast_to(bm_, ra.shape)
    total = jnp.zeros(ra.shape, jnp.int32)
    for stage in range(num_ecc + 1):
        k1, x1 = _mantissa_pair(ra)
        k2, x2 = _mantissa_pair(rb)
        m = (x1 << k2) + (x2 << k1)
        lead = jnp.int32(1) << (k1 + k2)
        if case_split and stage == num_ecc:
            p = jnp.where(m < lead, lead + m, 2 * m)
        else:
            p = lead + m                   # BB form: residual is x1*x2 exactly
        p = jnp.where((ra == 0) | (rb == 0), 0, p)
        total = total + p
        ra, rb = x1, x2
    return jnp.sum(total * sgn, axis=1)


def _kernel_scratch(a_ref, b_ref, o_ref, acc_ref, *, num_ecc: int,
                    case_split: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _signed_block_product(
        a_ref[...], b_ref[...], num_ecc=num_ecc, case_split=case_split
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def _kernel_output(a_ref, b_ref, o_ref, *, num_ecc: int, case_split: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += _signed_block_product(
        a_ref[...], b_ref[...], num_ecc=num_ecc, case_split=case_split
    )


def mitchell_matmul_kernel(
    a: Array,
    b: Array,
    *,
    num_ecc: int = 0,
    case_split: bool = True,
    block_m: int = 16,
    block_n: int = 128,
    block_k: int = 128,
    accum: str = "scratch",
    interpret: bool | None = None,
) -> Array:
    """Raw kernel entry: a (M, K) int32 signed, b (K, N) int32 signed -> int32.

    Shapes must be multiples of the block sizes (ops.py pads);
    interpret=None autodetects the backend (DESIGN.md §7). `accum` picks the
    K-reduction carry: a VMEM scratch tile with init/flush ('scratch', the
    default) or legacy in-place output accumulation ('output') -- module
    docstring, DESIGN.md §8.
    """
    if accum not in ACCUM_MODES:
        raise ValueError(f"accum must be one of {ACCUM_MODES}, got {accum!r}")
    interpret = resolve_interpret(interpret)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    grid = (m // block_m, n // block_n, k // block_k)
    scratch = accum == "scratch"
    kernel = functools.partial(
        _kernel_scratch if scratch else _kernel_output,
        num_ecc=num_ecc, case_split=case_split)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        grid=grid,
        name="mitchell_matmul",
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        scratch_shapes=(
            [pltpu.VMEM((block_m, block_n), jnp.int32)] if scratch else []),
        compiler_params=grid_compiler_params(
            ("parallel", "parallel", "arbitrary"), interpret),
        interpret=interpret,
    )(a.astype(jnp.int32), b.astype(jnp.int32))
