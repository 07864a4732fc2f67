"""Bit-exact behaviour of the multiplier family (paper Tables 1, 6, 7)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.karatsuba import exact_base, kom, op_counts
from repro.core.lns import decode, encode, lns_multiply
from repro.core.mitchell import (babic_bb, babic_ecc, mitchell,
                                 mitchell_corrected, mitchell_residual_operands)
from repro.core.odma import odma, odma_exact_identity
from repro.core.refmlm import (const_base_mults, efmlm2, mlm2, refmlm,
                               refmlm_const)
from repro.filters import FILTER_NAMES, get_filter

A8 = jnp.arange(256, dtype=jnp.int32)[:, None]
B8 = jnp.arange(256, dtype=jnp.int32)[None, :]
TRUE8 = A8 * B8


def _grid(nbits):
    n = 1 << nbits
    a = jnp.arange(n, dtype=jnp.int32)[:, None] * jnp.ones((1, n), jnp.int32)
    b = jnp.arange(n, dtype=jnp.int32)[None, :] * jnp.ones((n, 1), jnp.int32)
    return a, b


class TestEFMLM2:
    def test_table1_all_16_combinations(self):
        """Paper Table 1: only 11b x 11b errs in plain MLM; EFMLM exact."""
        a, b = _grid(2)
        mlmp = mlm2(a, b)
        true = a * b
        errs = np.argwhere(np.asarray(mlmp != true))
        assert errs.tolist() == [[3, 3]]              # only 3*3
        assert int(mlmp[3, 3]) == 8                   # 1000b, paper's MLMP
        assert bool((efmlm2(a, b) == true).all())     # corrected: exact

    def test_correction_term_is_single_and(self):
        a, b = _grid(2)
        corr = efmlm2(a, b) - mlm2(a, b)
        expected = ((a >> 1) & a & (b >> 1) & b & 1)
        assert bool((corr == expected).all())


class TestREFMLM:
    @pytest.mark.parametrize("variant", ["kom4", "kom3"])
    def test_exhaustive_8bit_exact(self, variant):
        """Paper Table 6 'Proposed with EC': AER = MER = 0.00% (all 65536)."""
        p = refmlm(A8, B8, 8, variant=variant, base="efmlm")
        assert bool((p == TRUE8).all())

    @pytest.mark.parametrize("variant", ["kom4", "kom3"])
    def test_exhaustive_4bit_exact(self, variant):
        a, b = _grid(4)
        assert bool((refmlm(a, b, 4, variant=variant) == a * b).all())

    def test_without_correction_matches_paper_aer(self):
        """Paper Table 7: 'Proposed Without EC' 4x4 AER ~ 1.76%."""
        a, b = _grid(4)
        p = refmlm(a, b, 4, variant="kom4", base="mlm").astype(jnp.float32)
        true = (a * b).astype(jnp.float32)
        err = jnp.where(true > 0, (true - p) / true, 0.0)
        # nonzero-product combinations only (paper uses 134 unique pairs)
        aer = float(jnp.abs(err).sum() / (true > 0).sum()) * 100
        assert 1.0 < aer < 2.5          # paper: 1.7629%

    def test_16bit_spot(self):
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.integers(0, 1 << 16, (512,)), jnp.int32)
        b = jnp.asarray(rng.integers(0, 1 << 16, (512,)), jnp.int32)
        p = refmlm(a, b, 16).astype(jnp.uint32)
        true = (a.astype(jnp.uint32) * b.astype(jnp.uint32))
        assert bool((p == true).all())


#: every |coefficient| the filter bank multiplies by, 2-D and separable
BANK_COEFFS = sorted({abs(int(c)) for name in FILTER_NAMES
                      for taps in (get_filter(name).taps,
                                   get_filter(name).sep_row,
                                   get_filter(name).sep_col)
                      if taps is not None for c in np.ravel(taps)})


def _assert_const_equals_runtime(a, c, nbits, base):
    want = refmlm(a, jnp.full_like(a, c), nbits, base=base)
    got = refmlm_const(a, c, nbits, base=base)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                  err_msg=f"c={c} nbits={nbits} base={base}")


class TestREFMLMConst:
    """`refmlm_const` (a constant coefficient, zero-digit leaves dropped)
    is the runtime kom4 `refmlm` bit for bit, for both bases."""

    @pytest.mark.parametrize("base", ["efmlm", "mlm"])
    @pytest.mark.parametrize("nbits", [2, 4, 8])
    def test_every_pair(self, nbits, base):
        a, b = _grid(nbits)
        want = np.asarray(refmlm(a, b, nbits, base=base))
        col = a[:, 0]
        for c in range(1 << nbits):
            np.testing.assert_array_equal(
                np.asarray(refmlm_const(col, c, nbits, base=base)),
                want[:, c], err_msg=f"c={c}")

    @pytest.mark.parametrize("base", ["efmlm", "mlm"])
    def test_16bit_every_a_for_each_bank_coefficient(self, base):
        a = jnp.arange(1 << 16, dtype=jnp.int32)
        for c in BANK_COEFFS:
            _assert_const_equals_runtime(a, c, 16, base)

    @pytest.mark.parametrize("base", ["efmlm", "mlm"])
    def test_16bit_seeded_sample_of_c(self, base):
        rng = np.random.default_rng(16)
        a = jnp.arange(1 << 16, dtype=jnp.int32)
        for c in rng.integers(0, 1 << 16, 24):
            _assert_const_equals_runtime(a, int(c), 16, base)

    @pytest.mark.parametrize("base", ["efmlm", "mlm"])
    @pytest.mark.parametrize("nbits", [2, 4])
    def test_operands_wider_than_nbits(self, nbits, base):
        """8-bit pixels and coefficients through a 2- or 4-bit multiplier
        (the camera control runs REFMLM at 4 bits): the constant path
        masks both operands exactly as the runtime recursion does."""
        a = jnp.arange(256, dtype=jnp.int32)
        for c in range(64):
            _assert_const_equals_runtime(a, c, nbits, base)

    @pytest.mark.parametrize("nbits", [2, 4, 8, 16])
    def test_base_mults_count_the_non_zero_digits(self, nbits):
        for c in (0, 1, 2, 3, 4, 6, 36, 160, 255, 4095, 65535):
            digits = [(c >> s) & 3 for s in range(0, nbits, 2)]
            want = (int(c != 0) if nbits == 2
                    else sum(map(bool, digits)) * nbits // 2)
            assert const_base_mults(c, nbits) == want, c


class TestMitchellFamily:
    def test_error_always_nonneg_and_bounded(self):
        pm = mitchell(A8, B8, 8).astype(jnp.float32)
        true = TRUE8.astype(jnp.float32)
        err = true - pm
        assert float(err.min()) >= 0.0
        rel = jnp.where(true > 0, err / true, 0.0)
        assert float(rel.max()) <= 1.0 / 9.0 + 1e-6   # MER = 11.11%

    def test_paper_table6_error_rates(self):
        """AER ~3.8% / MER 11.11% (MA row), BB MER = 25%."""
        true = TRUE8.astype(jnp.float32)
        rel = lambda p: jnp.where(true > 0, (true - p.astype(jnp.float32)) / true, 0.0)
        ma = rel(mitchell(A8, B8, 8))
        assert abs(float(ma.max()) - 1 / 9) < 1e-3
        assert 0.03 < float(ma.mean()) < 0.045        # paper 3.82% at 16 bit
        bb = rel(babic_bb(A8, B8, 8))
        # sup of (f1*f2)/(1+f1)(1+f2)-ish error -> 25%; 8-bit grid peaks 24.8%
        assert abs(float(bb.max()) - 0.25) < 5e-3     # paper BB MER 25%

    def test_power_of_two_operands_exact(self):
        """Paper Fig. 2: powers of two make Mitchell exact."""
        a = jnp.asarray([1, 2, 4, 8, 16, 32, 64, 128], jnp.int32)[:, None]
        b = jnp.arange(256, dtype=jnp.int32)[None, :]
        assert bool((mitchell(a, b, 8) == a * b).all())
        assert bool((mitchell(b, a, 8) == b * a).all())

    def test_mitchell_eq14_correction_exact(self):
        assert bool((mitchell_corrected(A8, B8, 8) == TRUE8).all())

    def test_residual_identity(self):
        ra, rb = mitchell_residual_operands(A8, B8)
        assert bool((mitchell(A8, B8, 8) + ra * rb == TRUE8).all())

    def test_babic_ecc_monotone_and_exact_limit(self):
        true = TRUE8.astype(jnp.float32)
        prev = None
        for k in range(0, 8):
            p = babic_ecc(A8, B8, 8, num_ecc=k).astype(jnp.float32)
            err = float(jnp.abs(true - p).sum())
            if prev is not None:
                assert err <= prev + 1e-6
            prev = err
        assert bool((babic_ecc(A8, B8, 8, num_ecc=8) == TRUE8).all())


class TestODMA:
    def test_identity_exhaustive_8bit(self):
        assert bool((odma_exact_identity(A8, B8, 8) == TRUE8).all())

    def test_odma_better_than_mitchell(self):
        """Paper Table 6: ODMA AER (3.53%) < MA AER (3.82%)."""
        true = TRUE8.astype(jnp.float32)
        rel = lambda p: jnp.where(true > 0, (true - p.astype(jnp.float32)) / true, 0.0)
        assert float(rel(odma(A8, B8, 8)).mean()) < float(rel(mitchell(A8, B8, 8)).mean())


class TestKaratsubaGeneric:
    @pytest.mark.parametrize("variant", ["kom4", "kom3"])
    @pytest.mark.parametrize("base_w", [2, 4])
    def test_kom_exact_any_base(self, variant, base_w):
        p = kom(A8, B8, 8, base_nbits=base_w, base_fn=exact_base(base_w),
                variant=variant)
        assert bool((p == TRUE8).all())

    def test_op_counts_match_paper_decomposition(self):
        """Paper §3: 16x16 -> 64 2x2 multipliers (radix-2, 4-product)."""
        assert op_counts(16, 2, "kom4")["base_mults"] == 64
        assert op_counts(16, 2, "kom3")["base_mults"] == 27
        assert op_counts(8, 2, "kom4")["base_mults"] == 16
        assert op_counts(4, 2, "kom4")["base_mults"] == 4


class TestLNS:
    def test_encode_decode_roundtrip_mitchell_semantics(self):
        v = jnp.arange(1, 256, dtype=jnp.int32)
        c = encode(v, 8)
        assert bool((decode(c) == v).all())           # frac_bits >= nbits-1: exact

    def test_lns_multiply_matches_mitchell(self):
        from repro.core.mitchell import mitchell as mm
        a = jnp.arange(1, 64, dtype=jnp.int32)[:, None]
        b = jnp.arange(1, 64, dtype=jnp.int32)[None, :]
        ca = encode(jnp.broadcast_to(a, (63, 63)), 8, frac_bits=16)
        cb = encode(jnp.broadcast_to(b, (63, 63)), 8, frac_bits=16)
        prod = decode(lns_multiply(ca, cb))
        assert bool((prod == mm(a, b, 8)).all())
