"""REFMLM -- the paper's contribution: Recursive Error-Free Mitchell Log
Multiplier (paper §3, Table 2 algorithm).

  * 2x2 EFMLM base (§3.1): Mitchell on 2-bit operands; the only erroneous
    combination is 11b x 11b (3*3 -> 8 instead of 9), fixed by the single
    correction term  prod(z_i) = a1&a0&b1&b0  (eq. 23). The base is EXACT.
  * KOM recursion (§3.2): radix-2 decomposition of the n-bit multiply into
    half-width multiplies until the 2x2 base.

Two recursion variants are provided (see DESIGN.md §1 faithfulness notes):

  kom4 -- the paper's own algorithm (Table 2 steps 5-8): 4 sub-products per
          level; 16x16 -> 64 base multiplies, matching the paper's count.
  kom3 -- eq. 19's true Karatsuba form: 3 sub-products per level via
          (a_L - a_H)(b_H - b_L) with sign tracking; 16x16 -> 27 base
          multiplies. The beyond-paper default on TPU.

Base variants:

  efmlm -- error-corrected 2x2 base  => n x n product is EXACT (AER=MER=0,
           paper Tables 6/7 'Proposed with Error Correction').
  mlm   -- uncorrected 2x2 Mitchell  => error propagates through the
           recursion ('Proposed Without Error Correction', AER 1.76% @ 4x4).

Widths: nbits in {2, 4, 8, 16, 32}. The paper's largest is 16x16; 32x32 is
the same radix-2 recursion one order up, built from the same 2x2 base, for
the column pass of 16-bit pixels (DESIGN.md §5). Products are carried in
uint32 lanes from 16 bits up, so no x64 mode is required: exact at 16
bits, and modulo 2**32 at 32 bits -- exact wherever the true product is
below 2**32. A leaf whose weight is 2**32 or more contributes 0 modulo
2**32 and is never evaluated.

Evaluation strategies (DESIGN.md §7):

  flatten=True (default) -- digit-plane flattening: the recursion tree is
      *linear* in its 2x2 base products (every sub-product enters the result
      as  weight * sign * base(a_i, b_i)  for a static power-of-two weight),
      so all leaves execute as ONE stacked base call over a leading
      digit-plane axis -- 16 kernel-visible base calls collapse to 1 at
      8-bit kom4 (64 -> 1 at 16-bit). Bit-identical to the unrolled
      recursion by construction, asserted in tests/test_tap_paths.py.
  flatten=False -- the paper-literal Python-unrolled recursion, kept as the
      structural reference.

  refmlm_const -- kom4 with a constant operand (a filter tap): the walk of
      the leaves runs on the coefficient as Python ints, so every leaf whose
      coefficient digit is 0 (an exact 0) is dropped at trace time and the
      kept leaves fold the coefficient's half of the Mitchell formula.
      Bit-identical to `refmlm`; asserted in tests/test_core_multipliers.py.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.core.bitops import split_halves
from repro.core.mitchell import _check_width, _prod_dtype

SUPPORTED_WIDTHS = (2, 4, 8, 16, 32)

#: the product lanes' modulus from 16 bits up (uint32, `_prod_dtype`).
_LANE = 1 << 32


def mlm2(a: Array, b: Array | int) -> Array:
    """Uncorrected 2x2 Mitchell product (paper Table 1 MLMP column).

    Closed form on 2-bit operands: the only approximation error is 3*3 -> 8.
    Implemented via the integer Mitchell formula specialized to k in {0, 1}.
    A Python-int `b` is a constant digit (`_mlm2_const`).
    """
    a = a.astype(jnp.int32)
    if isinstance(b, int):
        return _mlm2_const(a, b)
    b = b.astype(jnp.int32)
    k1 = (a >> 1) & 1          # leading-one position for 2-bit operands
    k2 = (b >> 1) & 1
    x1 = a - jnp.where(a > 0, jnp.int32(1) << k1, 0)
    x2 = b - jnp.where(b > 0, jnp.int32(1) << k2, 0)
    m = (x1 << k2) + (x2 << k1)
    lead = jnp.int32(1) << (k1 + k2)
    p = jnp.where(m < lead, lead + m, 2 * m)
    return jnp.where((a == 0) | (b == 0), 0, p)


def _mlm2_const(a: Array, c: int) -> Array:
    """`mlm2(a, c)` for a constant `c`: the same formula, with c's leading
    one k2 and mantissa x2 worked out in Python, so only a's half (its
    leading one, mantissa, the m < lead select and the zero detector) is
    left for the device."""
    if c == 0:
        return jnp.zeros_like(a)
    k2 = (c >> 1) & 1
    x2 = c - (1 << k2)
    k1 = (a >> 1) & 1
    x1 = a - jnp.where(a > 0, jnp.int32(1) << k1, 0)
    m = x1 << k2 if k2 else x1
    if x2:
        m = m + (x2 << k1)
    lead = jnp.int32(1 << k2) << k1
    p = jnp.where(m < lead, lead + m, 2 * m)
    return jnp.where(a == 0, 0, p)


def efmlm2(a: Array, b: Array | int) -> Array:
    """Error-Free 2x2 Mitchell multiplier (paper §3.1, eq. 23).

    mlm2 plus the single-AND correction term  a1*a0*b1*b0  (adds 1 exactly for
    the 11b x 11b combination). Exact for all 16 operand combinations. A
    Python-int `b` is a constant digit: its half of the AND folds away.
    """
    a = a.astype(jnp.int32)
    if isinstance(b, int):
        if (b >> 1) & b & 1:
            return mlm2(a, b) + ((a >> 1) & a & 1)
        return mlm2(a, b)
    b = b.astype(jnp.int32)
    correction = (a >> 1) & a & (b >> 1) & b & 1
    return mlm2(a, b) + correction


def _recurse(a: Array, b: Array, nbits: int, base_fn, variant: str) -> Array:
    """Exact-structure KOM recursion; returns the 2*nbits-bit product
    (modulo 2**32 at 32 bits, where a term shifted by 32 or more is 0)."""
    if nbits == 2:
        return base_fn(a, b)
    half = nbits // 2
    dt = _prod_dtype(nbits)
    a_h, a_l = split_halves(a.astype(jnp.int32), nbits)
    b_h, b_l = split_halves(b.astype(jnp.int32), nbits)

    def sub(x, y):
        return _recurse(x, y, half, base_fn, variant).astype(dt)

    low = sub(a_l, b_l)
    high = sub(a_h, b_h)
    if variant == "kom4":
        # Paper Table 2 steps 5-8: mid1 = a_H*b_L, mid2 = a_L*b_H.
        mid = sub(a_h, b_l) + sub(a_l, b_h)
    elif variant == "kom3":
        # Eq. 18/19: a_L*b_H + a_H*b_L = low + high + (a_L - a_H)(b_H - b_L),
        # with the cross term sign-tracked so the base stays unsigned; the
        # negative case subtracts, which wraps like the rest in uint32.
        dl = a_l - a_h
        dr = b_h - b_l
        sign = jnp.sign(dl) * jnp.sign(dr)
        t = sub(jnp.abs(dl), jnp.abs(dr))
        mid = low + high + jnp.where(sign < 0, jnp.zeros_like(t) - t, t)
    else:
        raise ValueError(f"unknown variant {variant!r}")
    out = low + (mid << half)
    return out + (high << nbits) if nbits < 32 else out


def _leaves(a: Array, b: Array, nbits: int, variant: str, weight: int,
            sign, out: list) -> None:
    """Collect the digit-plane leaf terms of the KOM recursion.

    Appends (a2, b2, weight, sign) tuples: 2-bit operand planes whose base
    product contributes  weight * sign * base(a2, b2)  to the n-bit result.
    `weight` is a static int (a sum of the recursion's shifts -- e.g. the
    kom3 low term enters both at weight 1 and, via mid, at 2**half, so its
    leaf carries 1 + 2**half); `sign` is None (+1) or an int32 array in
    {-1, 0, 1} accumulated down nested kom3 cross terms.
    """
    if nbits == 2:
        out.append((a, b, weight, sign))
        return
    half = nbits // 2
    a_h, a_l = split_halves(a, nbits)
    b_h, b_l = split_halves(b, nbits)
    if variant == "kom4":
        # P = low + (mid1 + mid2) << half + high << nbits (Table 2 steps 5-8).
        _leaves(a_l, b_l, half, variant, weight, sign, out)
        _leaves(a_h, b_l, half, variant, weight << half, sign, out)
        _leaves(a_l, b_h, half, variant, weight << half, sign, out)
        _leaves(a_h, b_h, half, variant, weight << nbits, sign, out)
    elif variant == "kom3":
        # P = low + (low + high + s*t) << half + high << nbits (eq. 19):
        # low and high each fold into one leaf with a combined weight.
        _leaves(a_l, b_l, half, variant, weight * (1 + (1 << half)), sign, out)
        _leaves(a_h, b_h, half, variant,
                weight * ((1 << half) + (1 << nbits)), sign, out)
        dl = a_l - a_h
        dr = b_h - b_l
        s = jnp.sign(dl) * jnp.sign(dr)
        _leaves(jnp.abs(dl), jnp.abs(dr), half, variant, weight << half,
                s if sign is None else sign * s, out)
    else:
        raise ValueError(f"unknown variant {variant!r}")


def _recurse_flat(a: Array, b: Array, nbits: int, base_fn, variant: str) -> Array:
    """Digit-plane-flattened KOM: one stacked base call, then the weighted sum.

    Bit-identical to `_recurse`: the leaf weights are exactly the composed
    shifts of the recursion, the base products are <= 9, and the combining
    arithmetic is carried in the same product dtype (int32 below 16 bits,
    uint32 from 16) where the recursion's adds are already modular. In
    uint32 a weight counts modulo 2**32, and a leaf whose weight is 0 there
    (at 32 bits, every digit pair whose shifts sum to 32 or more) is never
    stacked. kom3's
    data-dependent sign is applied to the small leaf product first and split
    into positive/negative accumulators so no signed value is ever cast to
    uint32.
    """
    if nbits == 2:
        return base_fn(a, b)
    a = a.astype(jnp.int32)
    b = b.astype(jnp.int32)
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    leaves: list = []
    _leaves(jnp.broadcast_to(a, shape), jnp.broadcast_to(b, shape),
            nbits, variant, 1, None, leaves)
    dt = _prod_dtype(nbits)
    if dt == jnp.uint32:
        leaves = [(la, lb, w % _LANE, s) for la, lb, w, s in leaves
                  if w % _LANE]
    planes_a = jnp.stack([la for la, _, _, _ in leaves])
    planes_b = jnp.stack([lb for _, lb, _, _ in leaves])
    prods = base_fn(planes_a, planes_b)          # ONE (L, ...) base multiply
    pos = jnp.zeros(shape, dt)
    neg = jnp.zeros(shape, dt)
    for i, (_, _, weight, sign) in enumerate(leaves):
        w = jnp.asarray(weight, dt)
        if sign is None:
            pos = pos + w * prods[i].astype(dt)
        else:
            st = sign * prods[i].astype(jnp.int32)       # |st| <= 9
            pos = pos + w * jnp.where(st > 0, st, 0).astype(dt)
            neg = neg + w * jnp.where(st < 0, -st, 0).astype(dt)
    return pos - neg                 # modular in dt, result in [0, 2**2n)


def refmlm(
    a: Array,
    b: Array,
    nbits: int = 16,
    *,
    variant: str = "kom4",
    base: str = "efmlm",
    flatten: bool = True,
) -> Array:
    """The paper's recursive multiplier, vectorized over tensors.

    Args:
      a, b: non-negative integer arrays with values < 2**nbits.
      nbits: operand width, one of 2/4/8/16/32.
      variant: 'kom4' (paper-faithful 4-product split) or 'kom3' (true
        Karatsuba 3-product split).
      base: 'efmlm' (error-free base => exact product) or 'mlm' (uncorrected
        base => error propagates, the paper's ablation).
      flatten: evaluate all base multiplies as one stacked digit-plane call
        (default; bit-identical, far fewer kernel-visible ops) or as the
        paper-literal unrolled recursion.
    Returns:
      The 2*nbits-bit product (exact iff base='efmlm'); at 32 bits the
      product modulo 2**32 in uint32.
    """
    _check_width(nbits, max(SUPPORTED_WIDTHS))
    if nbits not in SUPPORTED_WIDTHS:
        raise ValueError(f"nbits must be one of {SUPPORTED_WIDTHS}, got {nbits}")
    base_fn = {"efmlm": efmlm2, "mlm": mlm2}[base]
    impl = _recurse_flat if flatten else _recurse
    return impl(a, b, nbits, base_fn, variant)


refmlm16 = partial(refmlm, nbits=16)


@lru_cache(maxsize=None)
def _const_leaves(c: int, nbits: int) -> tuple[tuple[int, int, int], ...]:
    """The kom4 leaves of  a * c  whose coefficient digit is non-zero, as
    (pixel digit shift, coefficient digit, weight) triples.

    Walks `_leaves` itself with `c` as a Python int. The pixel side is a
    probe: element i of `probe` has digit i set and no other, so each
    leaf's pixel plane is one-hot at the digit it reads. The split masks
    both sides to `nbits`, exactly as the runtime recursion does. A leaf
    whose weight is 2**32 or more adds 0 to the uint32 product (32 bits
    only) and is dropped with the zero digits.
    """
    probe = np.array([1 << s for s in range(0, nbits, 2)], np.int64)
    leaves: list = []
    _leaves(probe, c, nbits, "kom4", 1, None, leaves)
    return tuple((2 * int(np.argmax(pa)), int(pc), w)
                 for pa, pc, w, _ in leaves if pc and w < _LANE)


def const_base_mults(c: int, nbits: int) -> int:
    """Base products `refmlm_const(a, c, nbits)` evaluates per element."""
    return len(_const_leaves(int(c), nbits))


def refmlm_const(a: Array, c: int, nbits: int = 16, *,
                 base: str = "efmlm") -> Array:
    """`refmlm(a, c, nbits, variant='kom4', base=base)` for a constant
    non-negative Python int `c`, bit for bit.

    A leaf  weight * base(a_i, c_j)  with c_j = 0 is exactly 0 (both bases
    zero-detect, and the correction term ANDs c_j's bits), so only leaves
    with a non-zero coefficient digit are evaluated, each through the same
    base with the digit as a Python int. Dropping exact zeros from the
    modular sum in the product dtype leaves it unchanged, as does dropping a
    leaf whose weight is 0 modulo 2**32. A constant with no non-zero digit
    costs no base product at all.
    """
    _check_width(nbits, max(SUPPORTED_WIDTHS))
    if nbits not in SUPPORTED_WIDTHS:
        raise ValueError(f"nbits must be one of {SUPPORTED_WIDTHS}, got {nbits}")
    base_fn = {"efmlm": efmlm2, "mlm": mlm2}[base]
    a = a.astype(jnp.int32)
    if nbits == 2:
        return base_fn(a, int(c))
    dt = _prod_dtype(nbits)
    digits: dict[int, Array] = {}         # pixel planes, shared across c_j
    acc = None
    for shift, cj, weight in _const_leaves(int(c), nbits):
        if shift not in digits:
            digits[shift] = (a >> shift) & 3
        term = base_fn(digits[shift], cj).astype(dt) << (weight.bit_length() - 1)
        acc = term if acc is None else acc + term       # kom4 weights are 2**k
    return jnp.zeros(a.shape, dt) if acc is None else acc


def flat_base_mults(nbits: int) -> int:
    """Base products the flattened kom4 `refmlm` stacks per element: every
    digit pair but those whose weight is 0 modulo 2**32 (only at 32 bits,
    where 136 of the recursion's 256 remain)."""
    digits = range(nbits // 2)
    return sum(1 for i in digits for j in digits if 2 * (i + j) < 32)


def op_counts(nbits: int, variant: str = "kom4") -> dict[str, int]:
    """Analytic operation counts -- the TPU analogue of the paper's LUT table
    (Table 9): base 2x2 multiplies and word adds per n x n product."""
    if nbits == 2:
        return {"base_mults": 1, "adds": 0}
    half = nbits // 2
    sub = op_counts(half, variant)
    if variant == "kom4":
        # 4 sub-products, 3 combining adds.
        return {"base_mults": 4 * sub["base_mults"], "adds": 4 * sub["adds"] + 3}
    # kom3: 3 sub-products; 2 operand subs + 2 adds for mid + 2 combining adds.
    return {"base_mults": 3 * sub["base_mults"], "adds": 3 * sub["adds"] + 6}
