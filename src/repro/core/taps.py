"""Tap multipliers: the method -> elementwise-product mapping of the conv
kernels (DESIGN.md §7).

`tap_multiplier(method)` multiplies two arrays of non-negative ints through
the selected paper multiplier; `const_tap_multiplier(method)` is the same
product with a constant Python-int coefficient, bit for bit, which a
kernel uses for taps it can read at trace time (REFMLM then drops the base
products whose coefficient digit is 0). One definition serves the conv
kernels and the pure jnp oracles (`repro.filters.ref`).
"""
from __future__ import annotations

import re

import jax.numpy as jnp

from repro.core.mitchell import babic_ecc as _babic_ecc
from repro.core.mitchell import MAX_NBITS
from repro.core.mitchell import mitchell as _mitchell
from repro.core.odma import odma as _odma
from repro.core.refmlm import refmlm as _refmlm
from repro.core.refmlm import SUPPORTED_WIDTHS
from repro.core.refmlm import refmlm_const as _refmlm_const

METHODS = ("exact", "refmlm", "refmlm_nc", "mitchell", "odma")  # + mitchell_ecc{k}


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


__all__ = ["METHODS", "const_tap_multiplier", "method_max_nbits",
           "tap_multiplier"]
