"""Mitchell logarithmic multipliers (paper §2.1/§2.2) and the Babic iterative
basic-block family (paper baseline [18], BB+kECC), vectorized over tensors.

Integer-domain formulation (exact fixed point, no floats):
  a = 2^k1 + x1  with integer mantissa x1 = a - 2^k1   (f1 = x1 / 2^k1)
  b = 2^k2 + x2

  Mitchell (MA, eq. 8):
    m = (x1 << k2) + (x2 << k1)            # = 2^(k1+k2) (f1 + f2)
    P = 2^(k1+k2) + m          if m <  2^(k1+k2)    (f1+f2 < 1)
      = 2 * m                  if m >= 2^(k1+k2)    (f1+f2 >= 1)

  Exact residuals (eqs. 11-13):
    case f1+f2 <  1 :  P_true - P = x1 * x2
    case f1+f2 >= 1 :  P_true - P = (2^k1 - x1) * (2^k2 - x2)

  Babic basic block (BB) drops the case split:
    P_BB = 2^(k1+k2) + m           with residual  a*b - P_BB = x1 * x2  always,
  so k cascaded error-correction circuits (ECC) re-apply BB to the mantissa
  residues: P = BB(a,b) + BB(x1,x2) + BB(x1',x2') + ...  This reproduces the
  paper's BB+1ECC / BB+2ECC / BB+3ECC baselines (Tables 6-9).

All functions assume non-negative operands with bit width `nbits` <= 16 so
products fit a uint32 lane without requiring x64 mode (the paper's largest
multiplier is 16x16). Zero operands are handled with the same zero-detector
semantics as the paper's architecture (product forced to 0).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import Array

from repro.core.bitops import leading_one_position

MAX_NBITS = 16


def _check_width(nbits: int, max_nbits: int = MAX_NBITS) -> None:
    """`nbits` within [2, `max_nbits`]: Mitchell's family stops at the
    paper's 16; REFMLM passes its own 32 (`repro.core.refmlm`)."""
    if not (2 <= nbits <= max_nbits):
        raise ValueError(f"nbits must be in [2, {max_nbits}], got {nbits}")


def _prod_dtype(nbits: int):
    # 2*nbits-bit products: int32 lanes while they fit, else uint32 --
    # exact up to 16 bits, modulo 2**32 above (REFMLM's 32-bit width).
    return jnp.int32 if 2 * nbits <= 31 else jnp.uint32


def characteristic_and_mantissa(x: Array) -> tuple[Array, Array]:
    """(k, mantissa) with x = 2^k + mantissa; (0, 0) for x == 0."""
    x = x.astype(jnp.int32)
    k = leading_one_position(x)
    m = x - jnp.where(x > 0, jnp.int32(1) << k, 0)
    return k, m


def mitchell(a: Array, b: Array, nbits: int = 16) -> Array:
    """Mitchell's algorithm (MA) product approximation, eq. 8.

    MER = 1/9 (11.11%); exact when either operand is a power of two or zero.
    """
    _check_width(nbits)
    dt = _prod_dtype(nbits)
    a = a.astype(jnp.int32)
    b = b.astype(jnp.int32)
    k1, x1 = characteristic_and_mantissa(a)
    k2, x2 = characteristic_and_mantissa(b)
    m = (x1.astype(dt) << k2) + (x2.astype(dt) << k1)
    lead = jnp.asarray(1, dt) << (k1 + k2)
    p = jnp.where(m < lead, lead + m, jnp.asarray(2, dt) * m)
    return jnp.where((a == 0) | (b == 0), jnp.asarray(0, dt), p)


def mitchell_residual_operands(a: Array, b: Array) -> tuple[Array, Array]:
    """Operands whose exact product equals the Mitchell (MA) error, eqs. 11/13.

    case f1+f2 < 1 : (x1, x2);  case f1+f2 >= 1 : (2^k1 - x1, 2^k2 - x2).
    Zero operands map to (0, 0).
    """
    a = a.astype(jnp.int32)
    b = b.astype(jnp.int32)
    k1, x1 = characteristic_and_mantissa(a)
    k2, x2 = characteristic_and_mantissa(b)
    m = (x1 << k2) + (x2 << k1)          # fits int32 for nbits <= 15 mantissas
    lead = jnp.int32(1) << (k1 + k2)
    carry = m >= lead
    ra = jnp.where(carry, (jnp.int32(1) << k1) - x1, x1)
    rb = jnp.where(carry, (jnp.int32(1) << k2) - x2, x2)
    zero = (a == 0) | (b == 0)
    return jnp.where(zero, 0, ra), jnp.where(zero, 0, rb)


def mitchell_corrected(a: Array, b: Array, nbits: int = 16) -> Array:
    """Mitchell's own analytic correction (eq. 14): MA + exact residual product.

    This is exact by construction -- the paper's point is that it needs a
    second *multiplier* for the residual product, which is the disadvantage
    REFMLM removes. Kept as a reference/oracle.
    """
    _check_width(nbits)
    dt = _prod_dtype(nbits)
    ra, rb = mitchell_residual_operands(a, b)
    return mitchell(a, b, nbits) + (ra.astype(dt) * rb.astype(dt))


def babic_bb(a: Array, b: Array, nbits: int = 16) -> Array:
    """Babic/Bulic basic block (no case split):  2^(k1+k2) + m.

    Residual is always x1*x2; MER = 25% (paper Table 6 row BB).
    """
    _check_width(nbits)
    dt = _prod_dtype(nbits)
    a = a.astype(jnp.int32)
    b = b.astype(jnp.int32)
    k1, x1 = characteristic_and_mantissa(a)
    k2, x2 = characteristic_and_mantissa(b)
    m = (x1.astype(dt) << k2) + (x2.astype(dt) << k1)
    lead = jnp.asarray(1, dt) << (k1 + k2)
    return jnp.where((a == 0) | (b == 0), jnp.asarray(0, dt), lead + m)


def babic_ecc(a: Array, b: Array, nbits: int = 16, num_ecc: int = 1) -> Array:
    """Iterative logarithmic multiplier: BB + `num_ecc` correction circuits.

    Each ECC stage applies BB to the mantissa residues of the previous stage
    (paper baseline [18]). num_ecc = 0 is plain BB. With num_ecc >= nbits the
    result is exact (residues run out of bits).
    """
    _check_width(nbits)
    dt = _prod_dtype(nbits)
    ra = a.astype(jnp.int32)
    rb = b.astype(jnp.int32)
    total = jnp.zeros(jnp.broadcast_shapes(ra.shape, rb.shape), dt)
    for _ in range(num_ecc + 1):
        total = total + babic_bb(ra, rb, nbits)
        k1, x1 = characteristic_and_mantissa(ra)
        k2, x2 = characteristic_and_mantissa(rb)
        ra, rb = x1, x2
    return total


def mitchell_truncated_float(a: Array, b: Array) -> Array:
    """Float-domain Mitchell for real-valued tensors (LNS research path).

    log2|a| ~ k + f via frexp-free piecewise-linear approx; returned product
    carries sign(a)*sign(b). Exact at powers of two, error <= 11.1% -- used by
    the approximate-training experiments, not by the bit-exact reproduction.
    """
    sa, sb = jnp.sign(a), jnp.sign(b)
    aa, ab = jnp.abs(a), jnp.abs(b)
    ea = jnp.floor(jnp.log2(jnp.where(aa > 0, aa, 1.0)))
    eb = jnp.floor(jnp.log2(jnp.where(ab > 0, ab, 1.0)))
    fa = aa / jnp.exp2(ea) - 1.0          # mantissa fraction in [0, 1)
    fb = ab / jnp.exp2(eb) - 1.0
    s = fa + fb
    p = jnp.where(s < 1.0, jnp.exp2(ea + eb) * (1.0 + s), jnp.exp2(ea + eb + 1.0) * s)
    return sa * sb * jnp.where((aa == 0) | (ab == 0), 0.0, p)
