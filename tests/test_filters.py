"""Filter-bank sweeps: every bank filter x multiplier vs the pure-jnp
oracle, the zero-error REFMLM claim on every filter, the separable ==
direct identity for exact multipliers (DESIGN.md §5), and the tiling
invariance of the §8 grid overhaul: every output is bit-identical across
row-band heights, column-tile widths, batch folds, and the autotuned
default.

Kernels run in interpret mode (CPU container; TPU is the target). Integer
outputs must match the oracle EXACTLY -- the filter datapath is pure-integer
like the paper's RTL.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.filters import FILTER_NAMES, apply_filter, get_filter
from repro.filters.bank import gaussian_kernel_1d, max_intermediate
from repro.filters.conv import choose_block_rows, second_pass_nbits
from repro.filters.ref import apply_filter_ref

RNG = np.random.default_rng(42)
BATCH = jnp.asarray(RNG.integers(0, 256, (2, 48, 40)), jnp.int32)


class TestBankVsOracle:
    @pytest.mark.parametrize("name", FILTER_NAMES)
    @pytest.mark.parametrize("method", ["exact", "refmlm", "mitchell",
                                        "mitchell_ecc2", "odma"])
    def test_bit_exact_vs_oracle(self, name, method):
        got = apply_filter(BATCH, name, method=method)
        want = apply_filter_ref(BATCH, name, method=method)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("name", FILTER_NAMES)
    def test_refmlm_identical_to_exact(self, name):
        """The paper's zero-error claim, extended to every bank filter."""
        exact = apply_filter(BATCH, name, method="exact")
        prop = apply_filter(BATCH, name, method="refmlm")
        np.testing.assert_array_equal(np.asarray(exact), np.asarray(prop))

    def test_refmlm_nc_ablation_differs_somewhere(self):
        """The uncorrected-base ablation must NOT be error-free on box3
        (otherwise the correction is vacuous). The mlm base errs only when
        both operands carry a '11' 2-bit chunk, so the probe filter must
        have such a coefficient -- box3's 7 = 0b111 qualifies; powers of
        two (Sobel, gaussian3) and 32/160 (sharpen3) do not."""
        exact = np.asarray(apply_filter(BATCH, "box3", method="exact"))
        nc = np.asarray(apply_filter(BATCH, "box3", method="refmlm_nc"))
        assert (exact != nc).any()


class TestSeparable:
    @pytest.mark.parametrize("name", [n for n in FILTER_NAMES
                                      if get_filter(n).separable])
    @pytest.mark.parametrize("method", ["exact", "refmlm"])
    def test_separable_equals_direct(self, name, method):
        """Outer-product tap tables + exact multipliers => the two-pass
        dataflow is bit-identical to the direct KxK window."""
        direct = apply_filter(BATCH, name, method=method, separable=False)
        sep = apply_filter(BATCH, name, method=method, separable=True)
        np.testing.assert_array_equal(np.asarray(direct), np.asarray(sep))

    def test_direct_table_is_outer_product(self):
        for name in FILTER_NAMES:
            spec = get_filter(name)
            if spec.separable:
                np.testing.assert_array_equal(
                    spec.taps, np.outer(spec.sep_col, spec.sep_row))

    def test_nonseparable_request_raises(self):
        with pytest.raises(ValueError, match="separable"):
            apply_filter(BATCH, "laplacian", separable=True)


#: (block_rows, block_cols, batch_fold) grid organizations the outputs must
#: be invariant to -- band taller than H (pads), narrow column tiles at the
#: 5x5 halo floor, folded and unfolded batches, non-divisor shapes.
TILINGS = (
    (8, 16, False),
    (16, 8, True),
    (64, None, True),
    (104, 24, True),
)


class TestTilingInvariance:
    @pytest.mark.parametrize("name", FILTER_NAMES)
    def test_every_filter_invariant_across_grids(self, name):
        """§8 guarantee: the grid organization is a pure throughput knob."""
        base = np.asarray(apply_filter(BATCH, name, method="refmlm"))
        for br, bc, fold in TILINGS:
            got = apply_filter(BATCH, name, method="refmlm", block_rows=br,
                               block_cols=bc, batch_fold=fold)
            np.testing.assert_array_equal(np.asarray(got), base,
                                          err_msg=f"{name} br={br} bc={bc} "
                                                  f"fold={fold}")

    @pytest.mark.parametrize("method", ["exact", "mitchell", "odma"])
    def test_approximate_methods_invariant_across_grids(self, method):
        """Tiling must not perturb approximation error either."""
        base = np.asarray(apply_filter(BATCH, "gaussian5", method=method))
        for br, bc, fold in TILINGS:
            got = apply_filter(BATCH, "gaussian5", method=method,
                               block_rows=br, block_cols=bc, batch_fold=fold)
            np.testing.assert_array_equal(np.asarray(got), base,
                                          err_msg=f"br={br} bc={bc} fold={fold}")

    @pytest.mark.parametrize("dataflow", ["direct", "two_pass", "fused"])
    def test_every_dataflow_invariant_across_grids(self, dataflow):
        kw = dict(separable=dataflow != "direct",
                  fused=dataflow == "fused") if dataflow != "direct" \
            else dict(separable=False)
        base = np.asarray(apply_filter(BATCH, "gaussian5", method="refmlm",
                                       **kw))
        for br, bc, fold in TILINGS:
            got = apply_filter(BATCH, "gaussian5", method="refmlm",
                               block_rows=br, block_cols=bc, batch_fold=fold,
                               **kw)
            np.testing.assert_array_equal(np.asarray(got), base,
                                          err_msg=f"{dataflow} br={br} "
                                                  f"bc={bc} fold={fold}")

    def test_recursion_impl_invariant_across_grids(self):
        base = np.asarray(apply_filter(BATCH, "gaussian3", method="refmlm"))
        for br, bc, fold in TILINGS[1:2]:
            got = apply_filter(BATCH, "gaussian3", method="refmlm",
                               block_rows=br, block_cols=bc, batch_fold=fold)
            np.testing.assert_array_equal(np.asarray(got), base)

    def test_narrow_column_tile_raises_below_halo_floor(self):
        with pytest.raises(ValueError, match="column halo"):
            apply_filter(BATCH, "gaussian5", method="refmlm", separable=False,
                         block_cols=4)


class TestShapesAndSpecs:
    def test_single_image_and_nhwc(self):
        one = apply_filter(BATCH[0], "gaussian3")
        nhwc = apply_filter(BATCH[..., None], "gaussian3")
        assert one.shape == BATCH.shape[1:]
        assert nhwc.shape == (*BATCH.shape, 1)
        np.testing.assert_array_equal(np.asarray(one), np.asarray(nhwc[0, ..., 0]))

    def test_row_padding_nonmultiple(self):
        imgs = jnp.asarray(RNG.integers(0, 256, (2, 50, 40)), jnp.int32)
        got = apply_filter(imgs, "gaussian5", method="refmlm")
        want = apply_filter_ref(imgs, "gaussian5", method="refmlm")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_choose_block_rows(self):
        assert choose_block_rows(256) == 128
        assert choose_block_rows(48) == 16
        assert choose_block_rows(50) == 8     # wrapper pads to a multiple

    def test_gaussian_1d_sums_to_scale(self):
        for ktaps, sigma in ((3, 1.0), (5, 1.0), (5, 1.5)):
            k = gaussian_kernel_1d(ktaps, sigma, scale=16)
            assert k.sum() == 16 and (k > 0).all()

    def test_coefficients_fit_the_8bit_datapath(self):
        for name in FILTER_NAMES:
            spec = get_filter(name)
            assert int(np.abs(spec.taps).max()) < 256, name
            if spec.separable:
                assert max_intermediate(spec) < (1 << 16), name

    def test_second_pass_nbits(self):
        assert second_pass_nbits(200, 8) == 8
        assert second_pass_nbits(4080, 16) == 16
        # 16-bit pixels: gaussian5's row sums reach 65535 * 16 = 1048560
        assert second_pass_nbits(1 << 16, 1) == 32
        assert second_pass_nbits(65535 * 16, 6) == 32
        with pytest.raises(ValueError, match="32-bit"):
            second_pass_nbits(1 << 32, 1)

    def test_unknown_filter_raises(self):
        with pytest.raises(ValueError, match="unknown filter"):
            get_filter("gabor")
