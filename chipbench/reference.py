"""Plain reference of the filters a configuration states.

What a correct answer is follows from the configuration's `method` and
`bit_depth`:

- `refmlm`, `exact`: exact integer correlation of the image with the
  outer product of the filter's column and row taps. REFMLM is
  error-free, so a correct served output equals it byte for byte.
- `mitchell`: Mitchell's algorithm (MA, eq. 8 of the paper) on every tap
  product, in the separable dataflow: a row pass with the `row` taps over
  the zero-padded image, then a column pass with the `col` taps over the
  zero-padded intermediate. An approximate product depends on the
  dataflow, so the configuration states this one in its `guarantee`.

Zero padding at the border gives 'same' size. The fixed-point epilogue
follows: `abs` for derivative filters, a rounding right shift by
`shift`, and a clip to [0, 2**bit_depth - 1], in the container of that
depth (uint8 up to 8 bits, else uint16). Written from the configuration
alone in int64 NumPy; it imports nothing of the program, so a change to
the program's multipliers, kernels or filter bank cannot change what it
answers.
"""
from __future__ import annotations

import numpy as np


def pixel_dtype(bit_depth: int) -> np.dtype:
    """The container of a pixel of `bit_depth` bits: uint8 up to 8, else
    uint16."""
    bit_depth = int(bit_depth)
    if not 1 <= bit_depth <= 16:
        raise ValueError(f"bit_depth must be in [1, 16], got {bit_depth}")
    return np.dtype(np.uint8 if bit_depth <= 8 else np.uint16)


def mitchell_product(a: np.ndarray, c: int) -> np.ndarray:
    """Mitchell's product (eq. 8) of non-negative integers `a` and `c`:
    with a = 2**k1 + x1 and c = 2**k2 + x2, m = x1*2**k2 + x2*2**k1, and
    P = 2**(k1+k2) + m where m < 2**(k1+k2), else 2*m; 0 where either
    operand is 0."""
    a = np.asarray(a, np.int64)
    c = int(c)
    if c == 0:
        return np.zeros_like(a)
    k2 = c.bit_length() - 1
    x2 = c - (1 << k2)
    safe = np.maximum(a, 1)
    # frexp gives a = f * 2**e with f in [0.5, 1): exact for a < 2**53
    k1 = np.frexp(safe.astype(np.float64))[1].astype(np.int64) - 1
    x1 = safe - (np.int64(1) << k1)
    m = (x1 << k2) + (np.int64(x2) << k1)
    lead = np.int64(1) << (k1 + k2)
    return np.where(a == 0, 0, np.where(m < lead, lead + m, 2 * m))


def _correlate_exact(img: np.ndarray, spec: dict) -> np.ndarray:
    """Exact correlation with the outer product of the column and row
    taps, in int64."""
    taps = np.outer(np.asarray(spec["col"], np.int64),
                    np.asarray(spec["row"], np.int64))
    kh, kw = taps.shape
    h, w = img.shape
    padded = np.pad(np.asarray(img, np.int64),
                    ((kh // 2, kh // 2), (kw // 2, kw // 2)))
    acc = np.zeros((h, w), np.int64)
    for di in range(kh):
        for dj in range(kw):
            if taps[di, dj]:
                acc += taps[di, dj] * padded[di:di + h, dj:dj + w]
    return acc


def _row_pass_mitchell(x: np.ndarray, taps) -> np.ndarray:
    """One pass of `taps` along the rows of the zero-padded `x`, each
    product sgn(x)*sgn(c)*P(|x|, |c|) by Mitchell's algorithm."""
    k, w = len(taps), x.shape[1]
    padded = np.pad(np.asarray(x, np.int64), ((0, 0), (k // 2, k // 2)))
    mag, sgn = np.abs(padded), np.sign(padded)
    acc = np.zeros(x.shape, np.int64)
    for d, c in enumerate(taps):
        if c:
            acc += (sgn[:, d:d + w] * int(np.sign(c))
                    * mitchell_product(mag[:, d:d + w], abs(int(c))))
    return acc


def _correlate_mitchell(img: np.ndarray, spec: dict) -> np.ndarray:
    """Row pass with the `row` taps, then column pass with the `col`
    taps, every product by Mitchell's algorithm, in int64."""
    tmp = _row_pass_mitchell(img, spec["row"])
    return _row_pass_mitchell(tmp.T, spec["col"]).T


#: the configuration's `method` -> its plain correlation.
CORRELATE = {"refmlm": _correlate_exact, "exact": _correlate_exact,
             "mitchell": _correlate_mitchell}


def filter_ref(img: np.ndarray, spec: dict, *, method: str = "refmlm",
               bit_depth: int = 8) -> np.ndarray:
    """(H, W) pixels -> (H, W) pixels of `bit_depth` bits, in their
    container, under the filter `spec` of a configuration served with
    `method`."""
    if method not in CORRELATE:
        raise ValueError(f"no plain reference for method {method!r}; "
                         f"methods with one: {sorted(CORRELATE)}")
    acc = CORRELATE[method](img, spec)
    if spec["post"] == "abs":
        acc = np.abs(acc)
    shift = int(spec["shift"])
    if shift > 0:
        acc = (acc + (1 << (shift - 1))) >> shift
    top = (1 << int(bit_depth)) - 1
    return np.clip(acc, 0, top).astype(pixel_dtype(bit_depth))


def kernel_size(spec: dict) -> tuple[int, int]:
    """(kh, kw) of a filter spec: the column and row tap counts."""
    return len(spec["col"]), len(spec["row"])
