"""Pallas TPU kernel: Karatsuba limb-decomposed wide-integer matmul.

The paper's REFMLM program (exact base multiplier + KOM recursion) re-priced
for the MXU: the systolic int8 x int8 -> int32 datapath is the exact base
unit; a wide (int16-class) matmul is decomposed into balanced limbs and
reconstructed from partial matmuls:

  schoolbook:  4 MXU passes  (w = 8 limbs, operand range ~ +-2^15)
  karatsuba:   3 MXU passes  (w = 7 limbs, operand range ~ +-2^13,
               middle pass multiplies (hi + lo) which fits int8)

The kernel emits THREE int32 accumulators (hh, mid, ll) so reconstruction /
rescale happens outside in f32 and the kernel stays bit-exact vs ref.py.

Tiling: classic (M/bm, N/bn, K/bk) grid; all limb blocks in VMEM. MXU dims
default to 128-multiples. The limbs enter the kernel as int8, the MXU's
integer operand type (Mosaic has no int32 x int32 matmul); balanced limbs
fit int8 by construction, and so does the Karatsuba middle operand
(hi + lo, w = 7).

Grid semantics (DESIGN.md §8): M and N are `parallel` output-tile axes, K
is the carried reduction (`arbitrary`). The three partial-product
accumulators carry in VMEM scratch tiles (init at k==0, flush at the last
k step; `accum='scratch'`, the default); `accum='output'` keeps the legacy
in-place output accumulation as the benchmark baseline. Bit-identical
either way.
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


def _block_products(ah_ref, al_ref, bh_ref, bl_ref, *, karatsuba: bool):
    ah, al = ah_ref[...], al_ref[...]
    bh, bl = bh_ref[...], bl_ref[...]
    dot = functools.partial(jnp.matmul, preferred_element_type=jnp.int32)
    hh = dot(ah, bh)
    ll = dot(al, bl)
    if karatsuba:
        # 3rd and final pass: (hi+lo) x (hi+lo) - hh - ll == the cross term.
        # The sum is formed in int32 (Mosaic has no int8 vector add) and
        # narrowed back: it fits int8 for w = 7 limbs.
        i32, i8 = jnp.int32, jnp.int8
        a_sum = (ah.astype(i32) + al.astype(i32)).astype(i8)
        b_sum = (bh.astype(i32) + bl.astype(i32)).astype(i8)
        mid = dot(a_sum, b_sum) - hh - ll
    else:
        mid = dot(ah, bl) + dot(al, bh)
    return hh, mid, ll


def _kernel_scratch(ah_ref, al_ref, bh_ref, bl_ref, hh_ref, mid_ref, ll_ref,
                    hh_acc, mid_acc, ll_acc, *, karatsuba: bool):
    accs = (hh_acc, mid_acc, ll_acc)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    hh, mid, ll = _block_products(ah_ref, al_ref, bh_ref, bl_ref,
                                  karatsuba=karatsuba)
    hh_acc[...] += hh
    mid_acc[...] += mid
    ll_acc[...] += ll

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        for out, acc in zip((hh_ref, mid_ref, ll_ref), accs):
            out[...] = acc[...]


def _kernel_output(ah_ref, al_ref, bh_ref, bl_ref, hh_ref, mid_ref, ll_ref,
                   *, karatsuba: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        hh_ref[...] = jnp.zeros_like(hh_ref)
        mid_ref[...] = jnp.zeros_like(mid_ref)
        ll_ref[...] = jnp.zeros_like(ll_ref)

    hh, mid, ll = _block_products(ah_ref, al_ref, bh_ref, bl_ref,
                                  karatsuba=karatsuba)
    hh_ref[...] += hh
    mid_ref[...] += mid
    ll_ref[...] += ll


def karatsuba_matmul_kernel(
    a_hi: Array,
    a_lo: Array,
    b_hi: Array,
    b_lo: Array,
    *,
    karatsuba: bool = True,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    accum: str = "scratch",
    interpret: bool | None = None,
) -> tuple[Array, Array, Array]:
    """Raw kernel entry over pre-decomposed limbs; returns (hh, mid, ll).
    interpret=None autodetects the backend (DESIGN.md §7); `accum` picks
    VMEM-scratch vs legacy in-place output accumulation (DESIGN.md §8)."""
    if accum not in ACCUM_MODES:
        raise ValueError(f"accum must be one of {ACCUM_MODES}, got {accum!r}")
    interpret = resolve_interpret(interpret)
    m, k = a_hi.shape
    k2, n = b_hi.shape
    assert k == k2 and m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    grid = (m // block_m, n // block_n, k // block_k)
    acc = jax.ShapeDtypeStruct((m, n), jnp.int32)
    a_spec = pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk))
    b_spec = pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j))
    o_spec = pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j))
    scratch = accum == "scratch"
    kernel = functools.partial(
        _kernel_scratch if scratch else _kernel_output, karatsuba=karatsuba)
    return pl.pallas_call(
        kernel,
        out_shape=(acc, acc, acc),
        grid=grid,
        name="karatsuba_matmul",
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=(o_spec, o_spec, o_spec),
        scratch_shapes=(
            [pltpu.VMEM((block_m, block_n), jnp.int32)] * 3 if scratch else []),
        compiler_params=grid_compiler_params(
            ("parallel", "parallel", "arbitrary"), interpret),
        interpret=interpret,
    )(
        a_hi.astype(jnp.int8),
        a_lo.astype(jnp.int8),
        b_hi.astype(jnp.int8),
        b_lo.astype(jnp.int8),
    )
