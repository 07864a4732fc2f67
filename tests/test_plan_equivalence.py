"""Differential harness for §11 execution plans: every `PlanConfig` --
random, degenerate, or adversarially poisoned -- must produce output
bit-identical to the untuned direct-dataflow reference (DESIGN.md §11).

Plans are pure throughput artifacts: the dataflow equivalence (§5) and
the grid-organization invariance (§8) are each argued and tested
separately, so a tuned plan composes only
bit-preserving choices. This file tests the *composition* end to end
through `apply_filter`'s plan resolution, across filters x methods
{exact, refmlm} x exec modes {local, streamed}, because that is the
surface a wrong cache entry would actually reach: a poisoned winner may
only ever cost time, never bytes.
"""
import json

import numpy as np
import pytest

from repro.filters import apply_filter
from repro.tuning import invalidate_cache, plan_key, store_cache
from repro.tuning.cache import cache_path
from repro.tuning.plans import PlanConfig, sanitize_plan

SHAPE = (3, 24, 20)                     # (n, h, w): small, halo-exercising


@pytest.fixture()
def tmp_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
    invalidate_cache()
    yield tmp_path
    invalidate_cache()


def _imgs(n, h, w):
    rng = np.random.default_rng(7)
    return rng.integers(0, 256, (n, h, w)).astype(np.int32)


def _run_plan(imgs, name, plan, *, method, exec_mode="local"):
    """Dispatch one fully-explicit plan the way the tuner does."""
    kw = dict(method=method, block_rows=plan.block_rows, block_cols=plan.block_cols,
              batch_fold=plan.batch_fold)
    if exec_mode == "streamed":
        kw.update(exec="streamed", tile=(16, 16), tile_batch=2)
    if plan.dataflow == "direct":
        return np.asarray(apply_filter(imgs, name, separable=False, **kw))
    if plan.dataflow == "two_pass":
        return np.asarray(apply_filter(imgs, name, separable=True,
                                       fused=False, **kw))
    return np.asarray(apply_filter(imgs, name, fused=True, **kw))


def _random_plan(rng, separable_ok: bool, h: int, w: int) -> PlanConfig:
    """One valid random plan, degenerate block shapes included
    (block_rows > H pads the whole image to one band; block_cols > W
    clamps to full width inside the pass)."""
    dataflow = rng.choice(
        ["direct", "two_pass", "fused"] if separable_ok else ["direct"])
    return PlanConfig(
        str(dataflow),
        int(rng.choice([8, 16, 24, h, 4 * h])),
        int(rng.choice([8, 16, w, 2 * w])),
        bool(rng.choice([False, True])),
    )


class TestRandomPlans:
    """Seeded deterministic sweep -- runs everywhere; the hypothesis
    property below widens the same check when hypothesis is installed."""

    @pytest.mark.parametrize("name,method", [
        ("gaussian5", "refmlm"), ("gaussian5", "exact"),
        ("sobel_x", "refmlm"), ("laplacian", "refmlm"),
        ("laplacian", "exact"),
    ])
    def test_random_plans_bit_identical_local(self, name, method, tmp_cache):
        n, h, w = SHAPE
        imgs = _imgs(n, h, w)
        ref = np.asarray(apply_filter(imgs, name, method=method,
                                      separable=False))
        rng = np.random.default_rng(hash((name, method)) % 2**32)
        from repro.filters import get_filter
        separable_ok = get_filter(name).separable
        for _ in range(4):
            plan = _random_plan(rng, separable_ok, h, w)
            out = _run_plan(imgs, name, plan, method=method)
            np.testing.assert_array_equal(out, ref, err_msg=str(plan))

    @pytest.mark.parametrize("name", ["gaussian5", "laplacian"])
    def test_random_plans_bit_identical_streamed(self, name, tmp_cache):
        n, h, w = SHAPE
        imgs = _imgs(n, h, w)
        ref = np.asarray(apply_filter(imgs, name, method="refmlm",
                                      separable=False))
        rng = np.random.default_rng(11)
        from repro.filters import get_filter
        separable_ok = get_filter(name).separable
        for _ in range(2):
            plan = _random_plan(rng, separable_ok, h, w)
            out = _run_plan(imgs, name, plan, method="refmlm",
                            exec_mode="streamed")
            np.testing.assert_array_equal(out, ref, err_msg=str(plan))

    def test_degenerate_blocks_bit_identical(self, tmp_cache):
        """The named degenerate corners, pinned (not left to the rng):
        one band taller than the whole batch, a tile wider than the
        image, and the shallow legal floor."""
        n, h, w = SHAPE
        imgs = _imgs(n, h, w)
        ref = np.asarray(apply_filter(imgs, "gaussian5", separable=False))
        for plan in (
            PlanConfig("fused", 16 * h, w, True),
            PlanConfig("two_pass", h, 2 * w, False),
            PlanConfig("direct", 8, 8, True),
        ):
            out = _run_plan(imgs, "gaussian5", plan, method="refmlm")
            np.testing.assert_array_equal(out, ref, err_msg=str(plan))


class TestPoisonedCache:
    def _poison(self, name, n, h, w, entry):
        path = cache_path()
        plans = {plan_key(name, n, h, w): entry}
        # tile-local re-entry under streamed exec resolves its own shape
        # keys -- poison the whole small-shape neighborhood too
        for tn in (1, 2, n):
            for (th, tw) in ((16, 16), (18, 18), (h, w), (h + 4, w + 4)):
                plans[plan_key(name, tn, th, tw)] = entry
        store_cache({}, plans)
        assert json.loads(path.read_text())["plans"]

    @pytest.mark.parametrize("exec_mode", ["local", "streamed"])
    def test_absurd_winner_only_costs_time(self, tmp_cache, exec_mode):
        """An adversarial committed winner -- worst dataflow, a band far
        taller than the image, a tile narrower than the halo floor --
        still yields identical bytes through default-argument
        `apply_filter`."""
        n, h, w = SHAPE
        imgs = _imgs(n, h, w)
        ref = np.asarray(apply_filter(imgs, "gaussian5", separable=False))
        self._poison("gaussian5", n, h, w, {
            "dataflow": "direct",
            "block_rows": 10_000, "block_cols": 4, "batch_fold": True,
            "us_per_call": 1.0})
        kw = ({"exec": "streamed", "tile": (16, 16), "tile_batch": 2}
              if exec_mode == "streamed" else {})
        out = np.asarray(apply_filter(imgs, "gaussian5", **kw))
        np.testing.assert_array_equal(out, ref)

    def test_malformed_entry_falls_back_to_defaults(self, tmp_cache):
        n, h, w = SHAPE
        imgs = _imgs(n, h, w)
        ref = np.asarray(apply_filter(imgs, "gaussian5", separable=False))
        self._poison("gaussian5", n, h, w,
                     {"dataflow": "systolic",
                      "block_rows": 8, "block_cols": 8, "batch_fold": False,
                      "us_per_call": 1.0})
        out = np.asarray(apply_filter(imgs, "gaussian5"))
        np.testing.assert_array_equal(out, ref)

    def test_sanitize_clamps_poisoned_blocks(self):
        clamped = sanitize_plan(
            PlanConfig("fused", 10_000, 4, False), 3, 24, 20, 5, 5)
        assert clamped is not None
        assert clamped.block_rows <= 24  # one band over the unfolded height
        assert clamped.block_cols >= 8   # the column-halo floor
        assert sanitize_plan(PlanConfig("systolic", 8, 8, False),
                             3, 24, 20, 5, 5) is None


class TestHypothesisProperty:
    """The same differential property, hypothesis-driven (skipped when
    hypothesis is not installed -- the seeded sweep above always runs)."""

    def test_any_valid_plan_is_bit_identical(self, tmp_cache):
        hypothesis = pytest.importorskip("hypothesis")
        st = pytest.importorskip("hypothesis.strategies")

        n, h, w = SHAPE
        imgs = _imgs(n, h, w)
        refs = {
            name: np.asarray(apply_filter(imgs, name, method="refmlm",
                                          separable=False))
            for name in ("gaussian5", "laplacian")
        }

        @hypothesis.settings(max_examples=15, deadline=None)
        @hypothesis.given(
            name=st.sampled_from(["gaussian5", "laplacian"]),
            dataflow=st.sampled_from(["direct", "two_pass", "fused"]),
            block_rows=st.sampled_from([8, 16, 24, h, 4 * h]),
            block_cols=st.sampled_from([8, 16, w, 2 * w]),
            batch_fold=st.booleans(),
        )
        def check(name, dataflow, block_rows, block_cols, batch_fold):
            from repro.filters import get_filter
            if not get_filter(name).separable:
                dataflow = "direct"
            plan = PlanConfig(dataflow, block_rows, block_cols, batch_fold)
            out = _run_plan(imgs, name, plan, method="refmlm")
            np.testing.assert_array_equal(out, refs[name], err_msg=str(plan))

        check()
