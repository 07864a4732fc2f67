"""The three DESIGN.md §7 perf paths must be bit-identical to their
reference paths for every multiplier method, approximate ones included:

  * KCM product-table gather  == per-tap recursion (tables computed BY the
    selected multiplier, so approximation error is preserved bit-exactly);
  * digit-plane-flattened REFMLM == the paper-literal unrolled recursion;
  * fused separable kernel == two-pass separable == direct (the latter for
    exact multipliers, where the outer-product identity holds).

Kernels run in interpret mode (CPU container; TPU is the target).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kcm import (
    METHODS,
    filter_tables,
    product_table,
    tables_acc_bound,
    tap_multiplier,
)
from repro.core.refmlm import refmlm
from repro.filters import FILTER_NAMES, apply_filter, get_filter
from repro.filters.conv import conv2d_pass, fused_separable_pass
from repro.filters.ref import apply_filter_ref

METHODS_ALL = [*METHODS, "mitchell_ecc2"]
SEPARABLE = [n for n in FILTER_NAMES if get_filter(n).separable]
RNG = np.random.default_rng(7)
BATCH = jnp.asarray(RNG.integers(0, 256, (2, 48, 40)), jnp.int32)


class TestProductTables:
    @pytest.mark.parametrize("method", METHODS_ALL)
    @pytest.mark.parametrize("nbits", [2, 4, 8])
    def test_table_equals_multiplier_everywhere(self, method, nbits):
        """KCM ROM == the multiplier over the FULL operand range, for a
        spread of coefficients incl. 0 and the width's maximum."""
        mult = tap_multiplier(method)
        xs = jnp.arange(1 << nbits, dtype=jnp.int32)
        for coeff in sorted({0, 1, 3, (1 << nbits) - 1}):
            tab = product_table(method, coeff, nbits)
            want = np.asarray(mult(xs, jnp.full_like(xs, coeff), nbits))
            np.testing.assert_array_equal(tab, want, err_msg=f"coeff={coeff}")

    def test_negative_coefficient_bakes_sign(self):
        np.testing.assert_array_equal(product_table("refmlm", -7, 8),
                                      -product_table("refmlm", 7, 8))

    def test_filter_tables_rows_are_row_major(self):
        tabs = filter_tables("exact", np.array([[1, -2], [3, 4]]), 4)
        assert tabs.shape == (4, 16)
        np.testing.assert_array_equal(tabs[1], -2 * np.arange(16))
        np.testing.assert_array_equal(tabs[2], 3 * np.arange(16))

    def test_filter_tables_narrow_to_int16_when_products_fit(self):
        """§8 width analysis: small-product ROMs store at int16 (halved
        VMEM), wide ones stay int32; values identical either way."""
        small = filter_tables("exact", np.array([4, 8, 4]), 8)
        assert small.dtype == np.int16        # max |product| = 8*255 = 2040
        wide = filter_tables("exact", np.array([255]), 16)
        assert wide.dtype == np.int32         # 255 * 65535 >= 2**15
        np.testing.assert_array_equal(
            small, filter_tables("exact", np.array([4, 8, 4]), 8,
                                 narrow=False))

    def test_tables_acc_bound_is_sum_of_per_tap_maxima(self):
        tabs = filter_tables("exact", np.array([4, -8, 4]), 8)
        assert tables_acc_bound(tabs) == (4 + 8 + 4) * 255


class TestKCMConv:
    @pytest.mark.parametrize("method", METHODS_ALL)
    def test_kcm_equals_recursion_direct(self, method):
        """Gather path == recursion path on a filter with negative and zero
        coefficients (the signed-magnitude contract's hard cases)."""
        taps = get_filter("sharpen3").taps
        kw = dict(method=method, nbits=8, shift=5, post="clip")
        kcm = conv2d_pass(BATCH, taps, mult_impl="kcm", **kw)
        rec = conv2d_pass(BATCH, taps, mult_impl="recurse", **kw)
        np.testing.assert_array_equal(np.asarray(kcm), np.asarray(rec))

    @pytest.mark.parametrize("method", METHODS_ALL)
    def test_kcm_equals_recursion_signed_intermediate(self, method):
        """Second-pass shape: signed input values through a wider table."""
        inter = jnp.asarray(RNG.integers(-1020, 1021, (1, 16, 24)), jnp.int32)
        col = np.array([[1], [2], [1]])
        kw = dict(method=method, nbits=16, shift=0, post="none")
        kcm = conv2d_pass(inter, col, mult_impl="kcm", **kw)
        rec = conv2d_pass(inter, col, mult_impl="recurse", **kw)
        np.testing.assert_array_equal(np.asarray(kcm), np.asarray(rec))

    def test_auto_falls_back_under_jit(self):
        """Traced taps: 'auto' must pick the recursion path and still agree
        with the eager KCM result."""
        taps = get_filter("gaussian3").taps
        kw = dict(method="refmlm", nbits=8, shift=8, post="clip")
        jitted = jax.jit(lambda x, t: conv2d_pass(x, t, **kw))
        got = jitted(BATCH, jnp.asarray(taps))
        want = conv2d_pass(BATCH, taps, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_kcm_with_traced_taps_raises(self):
        with pytest.raises(ValueError, match="kcm"):
            jax.jit(lambda x, t: conv2d_pass(x, t, mult_impl="kcm"))(
                BATCH, jnp.ones((3, 3), jnp.int32))

    def test_unknown_mult_impl_raises(self):
        with pytest.raises(ValueError, match="mult_impl"):
            conv2d_pass(BATCH, get_filter("gaussian3").taps, mult_impl="rom")

    @pytest.mark.parametrize("method", ["refmlm", "mitchell"])
    def test_kcm_equals_recursion_under_tiled_folded_grid(self, method):
        """§8: the gather and recursion paths agree on every grid
        organization, not just the default."""
        taps = get_filter("sharpen3").taps
        kw = dict(method=method, nbits=8, shift=5, post="clip",
                  block_rows=16, block_cols=16, batch_fold=True)
        kcm = conv2d_pass(BATCH, taps, mult_impl="kcm", **kw)
        rec = conv2d_pass(BATCH, taps, mult_impl="recurse", **kw)
        np.testing.assert_array_equal(np.asarray(kcm), np.asarray(rec))


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

    def test_fused_recurse_equals_fused_kcm(self):
        kw = dict(method="refmlm", nbits=8, nbits2=16, shift=8, post="clip")
        kcm = fused_separable_pass(BATCH, np.array([1, 4, 6, 4, 1]),
                                   np.array([1, 4, 6, 4, 1]),
                                   mult_impl="kcm", **kw)
        rec = fused_separable_pass(BATCH, np.array([1, 4, 6, 4, 1]),
                                   np.array([1, 4, 6, 4, 1]),
                                   mult_impl="recurse", **kw)
        np.testing.assert_array_equal(np.asarray(kcm), np.asarray(rec))

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


class TestCompiledResolution:
    """Mosaic lowers only 2-D gathers, so compiled passes never take the
    KCM ROM lookup: 'auto' resolves to the bit-identical recursion, and an
    explicit 'kcm' fails with a clear error before any compile."""

    TAPS = np.asarray(get_filter("gaussian3").taps)

    @pytest.mark.parametrize("interpret,want", [(True, "kcm"),
                                                (False, "recurse")])
    def test_auto_follows_the_interpret_flag(self, interpret, want):
        from repro.filters.conv import _resolve_mult_impl
        assert _resolve_mult_impl("auto", self.TAPS,
                                  interpret=interpret) == want

    def test_explicit_kcm_on_compiled_pass_raises(self):
        with pytest.raises(ValueError, match="Mosaic cannot lower"):
            conv2d_pass(BATCH, self.TAPS, mult_impl="kcm", interpret=False)
        spec = get_filter("gaussian5")
        with pytest.raises(ValueError, match="Mosaic cannot lower"):
            fused_separable_pass(BATCH, spec.sep_row, spec.sep_col,
                                 mult_impl="kcm", interpret=False)
        with pytest.raises(ValueError, match="Mosaic cannot lower"):
            apply_filter(BATCH, "gaussian5", mult_impl="kcm",
                         interpret=False)
