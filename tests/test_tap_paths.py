"""The DESIGN.md §7 tap-product forms and perf paths must be bit-identical
to their reference paths for every multiplier method, approximate ones
included:

  * baked taps (`const_tap_multiplier`, the form every compiled pass with
    concrete taps takes) == traced taps in SMEM (`tap_multiplier`);
  * digit-plane-flattened REFMLM == the paper-literal unrolled recursion;
  * fused separable kernel == two-pass separable == direct (the latter for
    exact multipliers, where the outer-product identity holds).

A default call under the interpreter takes the baked form, the kernel the
chip compiles. Kernels run in interpret mode (CPU container; TPU is the
target).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.refmlm import refmlm
from repro.core.taps import METHODS, const_tap_multiplier, tap_multiplier
from repro.filters import FILTER_NAMES, apply_filter, get_filter
from repro.filters import conv
from repro.filters.conv import conv2d_pass, fused_separable_pass
from repro.filters.ref import apply_filter_ref
from repro.obs.metrics import MetricsRegistry

METHODS_ALL = [*METHODS, "mitchell_ecc2"]
SEPARABLE = [n for n in FILTER_NAMES if get_filter(n).separable]
RNG = np.random.default_rng(7)
BATCH = jnp.asarray(RNG.integers(0, 256, (2, 48, 40)), jnp.int32)


def traced(fn, imgs, *taps):
    """`fn(imgs, *taps)` with the taps as jit arguments: they ride in SMEM."""
    return jax.jit(fn)(imgs, *(jnp.asarray(t, jnp.int32) for t in taps))


class TestConstTapMultiplier:
    @pytest.mark.parametrize("method", METHODS_ALL)
    @pytest.mark.parametrize("nbits", [2, 4, 8])
    def test_const_equals_multiplier_everywhere(self, method, nbits):
        """The baked product == the runtime product over the FULL operand
        range, for a spread of coefficients incl. 0 and the width's
        maximum."""
        mult = tap_multiplier(method)
        const = const_tap_multiplier(method)
        xs = jnp.arange(1 << nbits, dtype=jnp.int32)
        for coeff in sorted({0, 1, 3, (1 << nbits) - 1}):
            got = np.asarray(const(xs, coeff, nbits))
            want = np.asarray(mult(xs, jnp.full_like(xs, coeff), nbits))
            np.testing.assert_array_equal(got, want, err_msg=f"coeff={coeff}")


class TestBakedEqualsSMEM:
    @pytest.mark.parametrize("method", METHODS_ALL)
    def test_baked_equals_smem_direct(self, method):
        """Baked taps == SMEM taps on a filter with negative and zero
        coefficients (the signed-magnitude contract's hard cases)."""
        taps = get_filter("sharpen3").taps
        kw = dict(method=method, nbits=8, shift=5, post="clip")
        baked = conv2d_pass(BATCH, taps, **kw)
        smem = traced(lambda x, t: conv2d_pass(x, t, **kw), BATCH, taps)
        np.testing.assert_array_equal(np.asarray(baked), np.asarray(smem))

    @pytest.mark.parametrize("method", METHODS_ALL)
    def test_baked_equals_smem_signed_intermediate(self, method):
        """Second-pass shape: signed input values at a wider multiplier."""
        inter = jnp.asarray(RNG.integers(-1020, 1021, (1, 16, 24)), jnp.int32)
        col = np.array([[1], [2], [1]])
        kw = dict(method=method, nbits=16, shift=0, post="none")
        baked = conv2d_pass(inter, col, **kw)
        smem = traced(lambda x, t: conv2d_pass(x, t, **kw), inter, col)
        np.testing.assert_array_equal(np.asarray(baked), np.asarray(smem))

    def test_jit_over_taps_equals_eager(self):
        """Taps traced by a caller's own jit take the SMEM form and still
        agree with the eager, baked result."""
        taps = get_filter("gaussian3").taps
        kw = dict(method="refmlm", nbits=8, shift=8, post="clip")
        jitted = jax.jit(lambda x, t: conv2d_pass(x, t, **kw))
        got = jitted(BATCH, jnp.asarray(taps))
        want = conv2d_pass(BATCH, taps, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("method", ["refmlm", "mitchell"])
    def test_baked_equals_smem_under_tiled_folded_grid(self, method):
        """§8: the baked and SMEM forms agree on every grid organization,
        not just the default."""
        taps = get_filter("sharpen3").taps
        kw = dict(method=method, nbits=8, shift=5, post="clip",
                  block_rows=16, block_cols=16, batch_fold=True)
        baked = conv2d_pass(BATCH, taps, **kw)
        smem = traced(lambda x, t: conv2d_pass(x, t, **kw), BATCH, taps)
        np.testing.assert_array_equal(np.asarray(baked), np.asarray(smem))


class TestFlattenedREFMLM:
    @pytest.mark.parametrize("variant", ["kom4", "kom3"])
    @pytest.mark.parametrize("base", ["efmlm", "mlm"])
    @pytest.mark.parametrize("nbits", [4, 8])
    def test_exhaustive_flat_equals_unrolled(self, variant, base, nbits):
        n = 1 << nbits
        a = jnp.arange(n, dtype=jnp.int32)[:, None]
        b = jnp.arange(n, dtype=jnp.int32)[None, :]
        flat = refmlm(a, b, nbits, variant=variant, base=base, flatten=True)
        ref = refmlm(a, b, nbits, variant=variant, base=base, flatten=False)
        np.testing.assert_array_equal(np.asarray(flat), np.asarray(ref))

    @pytest.mark.parametrize("variant", ["kom4", "kom3"])
    @pytest.mark.parametrize("base", ["efmlm", "mlm"])
    def test_16bit_sampled_flat_equals_unrolled(self, variant, base):
        a = jnp.asarray(RNG.integers(0, 1 << 16, 4096), jnp.int32)
        b = jnp.asarray(RNG.integers(0, 1 << 16, 4096), jnp.int32)
        flat = refmlm(a, b, 16, variant=variant, base=base, flatten=True)
        ref = refmlm(a, b, 16, variant=variant, base=base, flatten=False)
        np.testing.assert_array_equal(np.asarray(flat), np.asarray(ref))
        if base == "efmlm":     # and still exact, per the paper's claim
            true = (np.asarray(a, np.uint64) * np.asarray(b, np.uint64))
            np.testing.assert_array_equal(np.asarray(flat, np.uint64), true)


class TestFusedSeparable:
    @pytest.mark.parametrize("name", SEPARABLE)
    @pytest.mark.parametrize("method", METHODS_ALL)
    def test_fused_equals_two_pass(self, name, method):
        fused = apply_filter(BATCH, name, method=method, separable=True,
                             fused=True)
        two = apply_filter(BATCH, name, method=method, separable=True,
                           fused=False)
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(two))

    @pytest.mark.parametrize("name", SEPARABLE)
    def test_fused_equals_direct_for_exact(self, name):
        """Outer-product taps + exact multiplier: all three dataflows agree."""
        for method in ("exact", "refmlm"):
            fused = apply_filter(BATCH, name, method=method, fused=True)
            direct = apply_filter(BATCH, name, method=method, separable=False)
            np.testing.assert_array_equal(np.asarray(fused), np.asarray(direct))

    def test_fused_baked_equals_fused_smem(self):
        kw = dict(method="refmlm", nbits=8, nbits2=16, shift=8, post="clip")
        taps = np.array([1, 4, 6, 4, 1])
        baked = fused_separable_pass(BATCH, taps, taps, **kw)
        smem = traced(lambda x, r, c: fused_separable_pass(x, r, c, **kw),
                      BATCH, taps, taps)
        np.testing.assert_array_equal(np.asarray(baked), np.asarray(smem))

    def test_fused_row_padding_nonmultiple(self):
        """Band padding + halo + crop compose on a non-multiple height."""
        imgs = jnp.asarray(RNG.integers(0, 256, (2, 50, 40)), jnp.int32)
        got = apply_filter(imgs, "gaussian5", method="refmlm", fused=True)
        want = apply_filter_ref(imgs, "gaussian5", method="refmlm")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_fused_on_direct_filter_raises(self):
        with pytest.raises(ValueError, match="separable"):
            apply_filter(BATCH, "laplacian", fused=True)

    def test_fused_explicit_shallow_block_rows_raises(self):
        """Explicit grid values win or fail loud -- never silently clamped."""
        taps = np.array([1, 4, 6, 4, 1])
        with pytest.raises(ValueError, match="row halo"):
            fused_separable_pass(BATCH, taps, taps, block_rows=2)

    def test_fused_invariant_under_column_tiles_and_fold(self):
        """§8: the 2x2 paired-view halo of the tiled fused kernel is
        bit-identical to the full-width band."""
        kw = dict(method="refmlm", nbits=8, nbits2=16, shift=8, post="clip")
        taps = np.array([1, 4, 6, 4, 1])
        base = fused_separable_pass(BATCH, taps, taps, **kw)
        for br, bc, fold in ((16, 16, False), (24, 8, True), (112, 16, True)):
            got = fused_separable_pass(BATCH, taps, taps, block_rows=br,
                                       block_cols=bc, batch_fold=fold, **kw)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(base),
                                          err_msg=f"br={br} bc={bc} fold={fold}")


class TestDefaultCallIsTheChipKernel:
    """A default call under the interpreter takes the baked form that a
    compiled pass with concrete taps takes: the kernel it builds records
    `conv_tap_leaves` with coef='baked'."""

    @pytest.mark.parametrize("name,shape", [("gaussian5", (1, 11, 13)),
                                            ("sobel_x", (1, 13, 11)),
                                            ("sharpen3", (1, 11, 11))])
    def test_default_call_bakes_its_taps(self, name, shape, monkeypatch):
        # a fresh registry and a shape no other test traces: the series
        # seen are exactly those of the kernel this call builds
        monkeypatch.setattr(conv, "KERNEL_METRICS", MetricsRegistry())
        kernel = ("refmlm_conv_fused" if get_filter(name).separable
                  else "refmlm_conv_pass")
        imgs = jnp.asarray(RNG.integers(0, 256, shape), jnp.uint8)
        apply_filter(imgs, name)
        series = conv.KERNEL_METRICS.snapshot()["gauges"]["conv_tap_leaves"]
        assert series and all("coef=baked" in k and f"kernel={kernel}," in k
                              for k in series), series
