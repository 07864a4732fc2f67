"""Pixels wider than 8 bits: 16-bit containers through the 32x32 REFMLM
(DESIGN.md §5).

A uint16 image is answered in uint16: exact integer correlation with zero
padding, the rounding shift and `post`, clipped to [0, 65535]. Its row
sums reach 65535 * 16 for the smoothing filters, so the separable column
pass multiplies at 32 bits: the paper's radix-2 KOM recursion one order
above its 16x16, on the same error-free 2x2 base, in uint32 lanes (modulo
2**32, exact wherever the product fits). Every dataflow -- direct, two
passes, fused; taps baked or in SMEM -- must equal the plain int64
reference `plain_filter_ref`, on 12-bit CT-like images and on the full
16-bit range. The kernels run in interpret mode here; the baked-and-SMEM
checks of the separable dataflows live in `test_container16_two_pass.py`
and `test_container16_fused.py`, so test workers share them.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.refmlm import (
    const_base_mults,
    flat_base_mults,
    refmlm,
    refmlm_const,
)
from repro.filters import FILTER_BANK, FILTER_NAMES, apply_filter, get_filter
from repro.filters.bank import max_intermediate
from repro.filters.conv import (
    conv2d_pass,
    fused_separable_pass,
    second_pass_nbits,
)
from repro.filters.pipeline import filter_nbits
from repro.filters.ref import plain_filter_ref

RNG = np.random.default_rng(16)
#: one batch of both depths: two 12-bit CT-like images, then two over the
#: full 16-bit range, the first at the container's top on every pixel
IMGS = np.concatenate([RNG.integers(0, 1 << 12, (2, 9, 12)),
                       RNG.integers(0, 1 << 16, (2, 9, 12))]).astype(np.uint16)
IMGS[2] = 65535
FLOWS = [(name, flow) for name in FILTER_NAMES
         for flow in (("direct", "two_pass", "fused")
                      if get_filter(name).separable else ("direct",))]


# ------------------------------------------------------- the 32x32 REFMLM
def _fits_32(n: int) -> tuple[np.ndarray, np.ndarray]:
    """Seeded uint32 operand pairs whose product is below 2**32, wide
    operands among them (up to 2**32 - 1 against a factor of 1)."""
    a = RNG.integers(0, 1 << 21, n, dtype=np.uint64)
    b = RNG.integers(0, 1 << 11, n, dtype=np.uint64)
    wide = RNG.integers(1, 1 << 32, n // 4, dtype=np.uint64)
    cap = ((1 << 32) - 1) // wide
    other = (RNG.random(n // 4) * (cap + 1)).astype(np.uint64)
    a, b = np.concatenate([a, wide]), np.concatenate([b, other])
    a[:2], b[:2] = [(1 << 32) - 1, 65535], [1, 65537]
    assert (a * b < (1 << 32)).all()
    return a.astype(np.uint32), b.astype(np.uint32)


@pytest.mark.parametrize("variant", ["kom4", "kom3"])
@pytest.mark.parametrize("flatten", [True, False])
def test_refmlm_32_is_exact_where_the_product_fits(variant, flatten):
    a, b = _fits_32(2000)
    got = np.asarray(refmlm(jnp.asarray(a), jnp.asarray(b), 32,
                            variant=variant, flatten=flatten))
    assert got.dtype == np.uint32
    np.testing.assert_array_equal(got.astype(np.uint64),
                                  a.astype(np.uint64) * b.astype(np.uint64))


def test_refmlm_32_flattened_equals_unrolled_modulo_2_32():
    """Past 2**32 both evaluations agree on the product modulo 2**32."""
    a = RNG.integers(0, 1 << 31, 500).astype(np.uint32)
    b = RNG.integers(0, 1 << 31, 500).astype(np.uint32)
    for variant in ("kom4", "kom3"):
        flat, unrolled = (np.asarray(refmlm(jnp.asarray(a), jnp.asarray(b),
                                            32, variant=variant,
                                            flatten=f)) for f in (True, False))
        np.testing.assert_array_equal(flat, unrolled)
        want = (a.astype(np.uint64) * b.astype(np.uint64)) % (1 << 32)
        np.testing.assert_array_equal(flat.astype(np.uint64), want)


BANK_COEFFS = sorted({abs(int(c)) for spec in FILTER_BANK.values()
                      for taps in (spec.taps, spec.sep_row, spec.sep_col)
                      if taps is not None for c in np.ravel(taps)})


@pytest.mark.parametrize("c", BANK_COEFFS)
def test_refmlm_const_32_equals_refmlm_for_every_bank_coefficient(c):
    a = jnp.asarray(RNG.integers(0, 1 << 21, 4096), jnp.int32)
    const = np.asarray(refmlm_const(a, c, 32))
    np.testing.assert_array_equal(
        const, np.asarray(refmlm(a, jnp.full(a.shape, c, jnp.int32), 32)))
    np.testing.assert_array_equal(const.astype(np.int64),
                                  np.asarray(a, np.int64) * c)


def test_32_bit_leaf_counts():
    """Leaves of weight 2**32 or more are 0 in uint32 and never built: of
    the recursion's 256, the flattened kom4 stacks 136; gaussian5's baked
    column taps keep 16 x 6 non-zero digit pairs less pixel digit 15
    against coefficient digit 1 (taps 4 and 6 and 4)."""
    assert flat_base_mults(16) == 64 and flat_base_mults(32) == 136
    assert [const_base_mults(c, 32) for c in (1, 4, 6)] == [16, 15, 31]
    assert sum(const_base_mults(c, 16) for c in (1, 4, 6, 4, 1)) == 48


# ------------------------------------------------ filters on 16-bit pixels
def test_16_bit_container_widths():
    """The column pass is sized from the container's top: 32 bits for every
    separable filter of the bank on uint16 pixels; uint8 keeps its 16."""
    for name in FILTER_NAMES:
        spec = get_filter(name)
        want16 = (16, 32) if spec.separable else (16,)
        want8 = (8, 16) if spec.separable else (8,)
        assert filter_nbits(spec, 16, "uint16") == want16, name
        assert filter_nbits(spec, 8, "uint8") == want8, name
        if spec.separable:
            assert max_intermediate(spec, 65535) >= 1 << 16


def _taps(spec, flow):
    if flow == "direct":
        return (np.asarray(spec.taps, np.int32),)
    return (np.asarray(spec.sep_row, np.int32)[None, :],
            np.asarray(spec.sep_col, np.int32)[:, None])


def _run16(imgs, *taps, spec, flow):
    """One dataflow of `spec` on 16-bit pixels through the recursion."""
    kw = dict(method="refmlm", interpret=True)
    if flow == "direct":
        return conv2d_pass(imgs, taps[0], nbits=16, shift=spec.shift,
                           post=spec.post, top=65535, **kw)
    row, col = taps
    nb2 = second_pass_nbits(max_intermediate(spec, 65535),
                            int(np.abs(spec.sep_col).max()))
    assert nb2 == 32
    if flow == "fused":
        return fused_separable_pass(imgs, row, col, nbits=16, nbits2=nb2,
                                    shift=spec.shift, post=spec.post,
                                    top=65535, **kw)
    tmp = conv2d_pass(imgs, row, nbits=16, shift=0, post="none", **kw)
    return conv2d_pass(tmp, col, nbits=nb2, shift=spec.shift,
                       post=spec.post, top=65535, **kw)


@functools.lru_cache(maxsize=None)
def _plain(name):
    return plain_filter_ref(IMGS, name)


def check_baked_and_smem(name, flow):
    """One dataflow of `name` on `IMGS`, taps baked and taps traced into
    SMEM: byte-equal to each other and to the plain reference."""
    spec = get_filter(name)
    taps = _taps(spec, flow)
    imgs = jnp.asarray(IMGS, jnp.int32)
    run = lambda x, *t: _run16(x, *t, spec=spec, flow=flow)
    baked = np.asarray(run(imgs, *taps))
    smem = np.asarray(jax.jit(run)(imgs, *map(jnp.asarray, taps)))
    want = _plain(name)
    assert want.dtype == np.uint16
    np.testing.assert_array_equal(baked, smem)
    np.testing.assert_array_equal(baked.astype(np.uint16), want)


@pytest.mark.parametrize("name", FILTER_NAMES)
def test_direct_baked_and_smem_taps_equal_the_plain_reference(name):
    check_baked_and_smem(name, "direct")


@pytest.mark.parametrize("name,flow", FLOWS)
def test_apply_filter_answers_uint16_in_every_dataflow(name, flow):
    """The public entry point, default tap-product choice: a uint16 batch is
    answered in uint16 and equals the plain reference."""
    kw = {"direct": dict(separable=False),
          "two_pass": dict(separable=True, fused=False),
          "fused": dict(separable=True, fused=True)}[flow]
    if flow == "direct" and get_filter(name).separable:
        kw = dict(separable=False)
    out = np.asarray(apply_filter(IMGS, name, method="refmlm",
                                  nbits=16, interpret=True, **kw))
    assert out.dtype == np.uint16
    np.testing.assert_array_equal(out, _plain(name))


def test_uint8_answers_are_unchanged():
    """8-bit pixels keep their contract: uint8, clipped to 255, whatever
    integer dtype carries them."""
    imgs = RNG.integers(0, 256, (2, 9, 12))
    for name in FILTER_NAMES:
        want = plain_filter_ref(imgs.astype(np.uint8), name)
        assert want.dtype == np.uint8
        for dtype in (np.uint8, np.int32):
            out = np.asarray(apply_filter(imgs.astype(dtype), name,
                                          interpret=True))
            assert out.dtype == np.uint8
            np.testing.assert_array_equal(out, want)


def test_sobel_keeps_its_magnitude_past_the_stored_bits():
    """The program knows the container, not the bits stored in it: a Sobel
    magnitude on 12-bit pixels runs up to 4 * 4095 and is clipped at 65535,
    not at 4095 -- the documented gap against a 2**bit_depth - 1 clip."""
    step = np.zeros((1, 8, 8), np.uint16)
    step[..., 4:] = 4095
    out = np.asarray(apply_filter(step, "sobel_x", nbits=16, interpret=True))
    assert out.dtype == np.uint16
    assert out.max() == 4 * 4095 > 4095
    np.testing.assert_array_equal(out, plain_filter_ref(step, "sobel_x"))


def test_methods_without_the_width_and_scale_out_modes_refuse_uint16():
    img = IMGS[0]
    with pytest.raises(ValueError, match="'mitchell' has no 32-bit"):
        apply_filter(img, "gaussian5", method="mitchell", nbits=16,
                     interpret=True)
    for mode in ("sharded", "streamed"):
        with pytest.raises(ValueError, match="uint8 pixels only"):
            apply_filter(img, "gaussian5", nbits=16, exec=mode,
                         interpret=True)
