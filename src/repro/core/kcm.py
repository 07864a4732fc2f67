"""KCM -- constant-coefficient multiplier tables (DESIGN.md §7).

FPGA convolution engines rarely instantiate a general multiplier per tap:
filter coefficients are synthesis-time constants, so each tap becomes a
LUT/ROM-indexed *constant-coefficient multiplier* (KCM) -- the pixel value
addresses a precomputed product table (arXiv:1710.05154). This module is the
TPU analogue: for a given `(method, coeff, nbits)` we enumerate every
possible operand x in [0, 2**nbits) ONCE through the selected multiplier and
cache the resulting product table. The conv kernels then replace the per-tap
KOM recursion (16 base multiplies at 8-bit kom4) with a single vectorized
table gather.

Because the table is computed *by* the selected multiplier, approximation
error is preserved bit-exactly: KCM(mitchell)[x] == mitchell(x, c) for every
x, so the approximate methods stay byte-identical to their recursion path
(asserted in tests/test_kcm.py).

Sign convention: the coefficient's sign is baked into the table
(`table[x] = sign(c) * mult(x, |c|)`), so the kernel's signed-magnitude
contract  sign(c)*sign(t)*mult(|t|,|c|)  reduces to  sign(t)*table[|t|].

`tap_multiplier` (the method -> elementwise-product mapping) lives here so
the table builder shares one definition with the conv kernels and the pure
jnp oracles; `repro.filters.conv` re-exports it.
"""
from __future__ import annotations

import re
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mitchell import babic_ecc as _babic_ecc
from repro.core.mitchell import MAX_NBITS
from repro.core.mitchell import mitchell as _mitchell
from repro.core.odma import odma as _odma
from repro.core.refmlm import refmlm as _refmlm
from repro.core.refmlm import SUPPORTED_WIDTHS
from repro.core.refmlm import refmlm_const as _refmlm_const

METHODS = ("exact", "refmlm", "refmlm_nc", "mitchell", "odma")  # + mitchell_ecc{k}

#: widest multiplier a ROM is built for: 2**16 entries a coefficient. A
#: 32-bit pass (the column pass of 16-bit pixels) has no ROM.
KCM_MAX_NBITS = 16


def tap_multiplier(method: str):
    """method -> f(a, b, nbits): elementwise product of non-negative ints."""
    if method == "exact":
        return lambda a, b, nbits: a * b
    if method == "refmlm":
        return lambda a, b, nbits: _refmlm(a, b, nbits, variant="kom4", base="efmlm").astype(jnp.int32)
    if method == "refmlm_nc":   # 'Proposed Without Error Correction' ablation
        return lambda a, b, nbits: _refmlm(a, b, nbits, variant="kom4", base="mlm").astype(jnp.int32)
    if method == "mitchell":
        return lambda a, b, nbits: _mitchell(a, b, nbits).astype(jnp.int32)
    if m := re.fullmatch(r"mitchell_ecc(\d+)", method):
        n = int(m.group(1))
        return lambda a, b, nbits: _babic_ecc(a, b, nbits, num_ecc=n).astype(jnp.int32)
    if method == "odma":
        return lambda a, b, nbits: _odma(a, b, nbits).astype(jnp.int32)
    raise ValueError(f"unknown multiplier method {method!r}")


def method_max_nbits(method: str) -> int | None:
    """The widest multiplier `method` has: REFMLM's 32-bit recursion, the
    paper's 16 for the Mitchell family and ODMA, and None for 'exact',
    whose int32 product is exact wherever the conv's accumulator bound
    holds."""
    tap_multiplier(method)                   # unknown methods fail here
    if method == "exact":
        return None
    if method in ("refmlm", "refmlm_nc"):
        return max(SUPPORTED_WIDTHS)
    return MAX_NBITS


def const_tap_multiplier(method: str):
    """method -> f(a, c, nbits): `tap_multiplier(method)` with a constant
    non-negative Python-int coefficient `c`, bit for bit.

    The REFMLM methods run the kom4 recursion with the constant operand
    (`refmlm_const`: leaves whose coefficient digit is 0 are dropped); every
    other method gets `c` broadcast, exactly as a runtime coefficient is.
    """
    if method == "refmlm":
        return lambda a, c, nbits: _refmlm_const(a, c, nbits, base="efmlm").astype(jnp.int32)
    if method == "refmlm_nc":
        return lambda a, c, nbits: _refmlm_const(a, c, nbits, base="mlm").astype(jnp.int32)
    mult = tap_multiplier(method)
    return lambda a, c, nbits: mult(a, jnp.broadcast_to(jnp.int32(c), a.shape), nbits)


@lru_cache(maxsize=None)
def product_table(method: str, coeff: int, nbits: int) -> np.ndarray:
    """(2**nbits,) int32 KCM ROM:  table[x] = sign(coeff) * mult(x, |coeff|).

    Enumerates the full operand range through the selected multiplier once
    (cached per (method, coeff, nbits) across all filters and calls), so the
    gather path inherits the multiplier's exact error behaviour. The
    enumeration is forced eager (`ensure_compile_time_eval`): the ROM is a
    host-side constant even when the first request arrives inside a trace
    (e.g. under `shard_map` in the distributed path, DESIGN.md §9, where
    ops on constants would otherwise become tracers).
    """
    if nbits > KCM_MAX_NBITS:
        raise ValueError(f"a KCM ROM has at most {KCM_MAX_NBITS}-bit "
                         f"operands, got {nbits}")
    mult = tap_multiplier(method)
    with jax.ensure_compile_time_eval():
        xs = jnp.arange(1 << nbits, dtype=jnp.int32)
        cs = jnp.full_like(xs, abs(int(coeff)))
        tab = np.asarray(mult(xs, cs, nbits), dtype=np.int64)
    return (int(np.sign(coeff)) * tab).astype(np.int32)


def filter_tables(method: str, taps, nbits: int, *,
                  narrow: bool = True) -> np.ndarray:
    """Stacked per-tap KCM ROMs for a coefficient table.

    `taps` -- any integer array of trace-time-constant coefficients; returns
    (taps.size, 2**nbits), rows in C (row-major tap) order. With `narrow`
    (the default) the stack is stored at the narrowest width holding every
    product -- int16 when all |products| < 2**15 -- halving the VMEM
    footprint of the tile-resident ROMs; the conv kernel widens on
    accumulation only when the bound analysis requires it (DESIGN.md §8).
    """
    flat = np.asarray(taps, dtype=np.int64).reshape(-1)
    stack = np.stack([product_table(method, int(c), nbits) for c in flat])
    if narrow and np.abs(stack).max(initial=0) < (1 << 15):
        return stack.astype(np.int16)
    return stack


def tables_acc_bound(tables: np.ndarray) -> int:
    """Worst-case |accumulator| of a CSA tree fed by these ROMs: the sum of
    each tap's largest |product|. Exact (the tables ARE the realized
    products, approximation error included), so it sizes the narrowest safe
    accumulator width for the direct path the same way `second_pass_nbits`
    sizes the separable second pass (DESIGN.md §8)."""
    return int(np.abs(np.asarray(tables, np.int64)).max(axis=-1).sum())


__all__ = ["KCM_MAX_NBITS", "METHODS", "const_tap_multiplier",
           "filter_tables", "method_max_nbits", "product_table",
           "tables_acc_bound", "tap_multiplier"]
