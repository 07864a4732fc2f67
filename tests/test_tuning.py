"""The autotuner (repro.tuning): §8 block heuristics and resolution
order, the v2 cache schema (blocks + plans) with v1 migration, §11 plan
resolution precedence, and sweep determinism/reproducibility."""
import json

import pytest

from repro.tuning import (
    BlockConfig,
    PlanConfig,
    choose_block_rows,
    config_key,
    default_blocks,
    invalidate_cache,
    load_cache,
    load_plans,
    plan_key,
    resolve_blocks,
    resolve_plan,
    store_cache,
)
from repro.tuning.autotune import (
    DEFAULT_SWEEP,
    candidate_blocks,
    plan_candidates,
    sweep_plan,
    tune,
)
from repro.tuning.blocks import VMEM_BUDGET_BYTES, round_up, vmem_step_bytes
from repro.tuning.cache import backend_key, cache_path


@pytest.fixture()
def tmp_cache(tmp_path, monkeypatch):
    """Point the cache at an empty tmp dir for the duration of a test."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
    invalidate_cache()
    yield tmp_path
    invalidate_cache()


class TestHeuristic:
    def test_round_up(self):
        assert round_up(130, 8) == 136
        assert round_up(128, 8) == 128

    def test_choose_block_rows_is_conv_reexport(self):
        from repro.filters.conv import choose_block_rows as conv_cbr
        assert conv_cbr is choose_block_rows

    def test_small_batches_fold(self):
        cfg = default_blocks("direct", 8, 128, 128, 3, 3)
        assert cfg.batch_fold
        assert cfg.block_rows % 8 == 0
        # fewest-band cut of the folded tall height (8 * 130 = 1040 rows
        # exceeds MAX_BLOCK_ROWS once, so two bands)
        tall = 8 * (128 + 2)
        assert -(-tall // cfg.block_rows) == 2
        assert cfg.block_cols is None

    def test_single_image_does_not_fold(self):
        cfg = default_blocks("direct", 1, 128, 128, 5, 5)
        assert not cfg.batch_fold
        assert cfg.block_rows == choose_block_rows(128)

    def test_large_images_do_not_fold_but_do_tile_columns(self):
        cfg = default_blocks("direct", 4, 1024, 1024, 3, 3)
        assert not cfg.batch_fold          # 1024 rows per image is not small
        assert cfg.block_cols == 256

    def test_fused_halo_floor(self):
        cfg = default_blocks("fused", 2, 8, 64, 5, 5)
        assert cfg.block_rows >= 2 * (5 // 2)


class TestVmemBudget:
    """Compiled passes cap the band height by the per-step scoped-VMEM
    budget; interpreted passes keep the uncapped heuristic."""

    SHAPES = [("direct", 8, 128, 128, 3, 3), ("fused", 8, 128, 128, 5, 5),
              ("direct", 8, 480, 640, 5, 5), ("fused", 1, 1080, 1920, 5, 5),
              ("fused", 8, 364, 328, 3, 3), ("direct", 1, 512, 512, 1, 5),
              ("direct", 4, 256, 512, 5, 1), ("fused", 16, 64, 64, 5, 5)]

    @pytest.mark.parametrize("shape", SHAPES)
    def test_compiled_step_fits_budget(self, shape):
        kind, n, h, w, kh, kw = shape
        cfg = default_blocks(kind, n, h, w, kh, kw, interpret=False)
        assert cfg.block_rows % 8 == 0 and cfg.block_rows >= 8
        assert vmem_step_bytes(kind, cfg.block_rows, w, kh, kw,
                               cfg.block_cols) <= VMEM_BUDGET_BYTES

    def test_interpret_shapes_unchanged(self):
        # the CPU-interpret heuristic: 8 * 130 folded rows in two bands
        assert default_blocks("direct", 8, 128, 128, 3, 3,
                              interpret=True) == BlockConfig(520, None, True)
        assert default_blocks("direct", 8, 128, 128, 3, 3) == \
            default_blocks("direct", 8, 128, 128, 3, 3, interpret=True)
        assert default_blocks("direct", 1, 512, 512, 3, 3,
                              interpret=True).block_rows == 128

    def test_folded_batch_splits_into_more_bands_when_compiled(self):
        # the served 8 x 128 x 128 3x3 batch that overflowed v5e's 16 MiB
        cfg = default_blocks("direct", 8, 128, 128, 3, 3, interpret=False)
        assert cfg.batch_fold and cfg.block_cols is None
        assert cfg.block_rows < 520
        tall = 8 * (128 + 2)
        assert cfg.block_rows * -(-tall // cfg.block_rows) >= tall

    def test_unfolded_band_prefers_divisors_under_the_cap(self):
        cfg = default_blocks("fused", 1, 1024, 128, 5, 5, interpret=False)
        assert 1024 % cfg.block_rows == 0
        assert choose_block_rows(1024, 40) == 32
        assert choose_block_rows(1000, 4) == 8       # floor stays at 8

    def test_step_bytes_model(self):
        one = vmem_step_bytes("direct", 8, 128, 3, 3, None)
        assert vmem_step_bytes("direct", 16, 128, 3, 3, None) == 2 * one
        # column tiling doubles the views but narrows each to the tile
        tiled = vmem_step_bytes("direct", 8, 640, 3, 3, 256)
        full = vmem_step_bytes("direct", 8, 640, 3, 3, None)
        assert tiled < full
        # the fused kernel's in-kernel temporaries dominate
        assert vmem_step_bytes("fused", 8, 128, 5, 5, None) > one


class TestCandidates:
    @pytest.mark.parametrize("row", DEFAULT_SWEEP[:4])
    def test_candidates_valid_and_unique(self, row):
        kind, n, h, w, kh, kw, _ = row
        cands = list(candidate_blocks(kind, n, h, w, kh, kw))
        assert cands and len(cands) == len(set(cands))
        for cfg in cands:
            assert cfg.block_rows >= 8
            assert not (cfg.batch_fold and n == 1)


class TestCache:
    KEY = config_key("direct", 2, 48, 40, 3, 3, "kcm")
    ENTRY = {"block_rows": 24, "block_cols": 16, "batch_fold": True,
             "us_per_call": 1.0}

    def test_key_format(self):
        assert self.KEY == "direct/kcm/n2x48x40/k3x3"

    def test_store_load_roundtrip(self, tmp_cache):
        store_cache({self.KEY: self.ENTRY})
        assert load_cache()[self.KEY] == self.ENTRY

    def test_store_is_deterministic_under_pinned_timestamp(self, tmp_cache,
                                                           monkeypatch):
        monkeypatch.setenv("BENCH_TIMESTAMP", "2026-01-01T00:00:00Z")
        configs = {self.KEY: self.ENTRY,
                   config_key("fused", 1, 8, 8, 3, 3, "kcm"):
                       {"block_rows": 8, "block_cols": None,
                        "batch_fold": False, "us_per_call": 2.0}}
        path = store_cache(configs)
        first = path.read_bytes()
        store_cache(configs)
        assert path.read_bytes() == first
        meta = json.loads(first)["meta"]
        assert meta["generated"] == "2026-01-01T00:00:00Z"
        assert meta["backend"] == backend_key()

    def test_missing_or_corrupt_cache_falls_back(self, tmp_cache):
        assert load_cache() == {}
        cache_path().write_text("{not json")
        invalidate_cache()
        assert load_cache() == {}
        cfg = resolve_blocks("direct", 2, 48, 40, 3, 3, "kcm")
        assert cfg == default_blocks("direct", 2, 48, 40, 3, 3)


class TestResolve:
    def test_cached_entry_wins_over_heuristic(self, tmp_cache):
        store_cache({TestCache.KEY: TestCache.ENTRY})
        cfg = resolve_blocks("direct", 2, 48, 40, 3, 3, "kcm")
        assert cfg == BlockConfig(24, 16, True)

    def test_explicit_fields_win_over_cache(self, tmp_cache):
        """Explicit values always land; a cache entry that disagrees with
        any of them is rejected wholesale (its other fields were tuned for
        a different organization), so the rest comes from the heuristic."""
        store_cache({TestCache.KEY: TestCache.ENTRY})
        cfg = resolve_blocks("direct", 2, 48, 40, 3, 3, "kcm",
                             block_rows=8, batch_fold=False)
        heur = default_blocks("direct", 2, 48, 40, 3, 3, batch_fold=False)
        assert cfg == BlockConfig(8, heur.block_cols, False)

    def test_agreeing_explicit_fields_keep_the_cache(self, tmp_cache):
        store_cache({TestCache.KEY: TestCache.ENTRY})
        cfg = resolve_blocks("direct", 2, 48, 40, 3, 3, "kcm",
                             batch_fold=True)      # agrees with the entry
        assert cfg == BlockConfig(24, 16, True)

    def test_unfolding_a_fold_tuned_entry_gets_per_image_bands(self, tmp_cache):
        """The serial-batch baseline must not inherit a fold-sized tall
        band from a fold-tuned winner (it would pad every image to the
        tall height and silently waste ~Nx compute)."""
        key = config_key("direct", 8, 128, 128, 3, 3, "kcm")
        store_cache({key: {"block_rows": 1040, "block_cols": None,
                           "batch_fold": True, "us_per_call": 1.0}})
        cfg = resolve_blocks("direct", 8, 128, 128, 3, 3, "kcm",
                             batch_fold=False)
        assert cfg == BlockConfig(choose_block_rows(128), None, False)

    def test_other_impl_misses_the_cache(self, tmp_cache):
        store_cache({TestCache.KEY: TestCache.ENTRY})
        cfg = resolve_blocks("direct", 2, 48, 40, 3, 3, "recurse")
        assert cfg == default_blocks("direct", 2, 48, 40, 3, 3)


PLAN_ENTRY = {"dataflow": "two_pass", "mult_impl": "kcm",
              "block_rows": 136, "block_cols": 64, "batch_fold": True,
              "us_per_call": 500.0, "generated": "2026-01-01T00:00:00Z",
              "candidates": 54, "swept": 13, "pruned": 41}


class TestCacheV2:
    def test_plans_roundtrip(self, tmp_cache):
        key = plan_key("gaussian5", 2, 64, 64)
        store_cache({}, {key: PLAN_ENTRY})
        assert load_plans()[key] == PLAN_ENTRY
        data = json.loads(cache_path().read_text())
        assert data["meta"]["version"] == 2
        assert set(data) == {"meta", "blocks", "plans"}

    def test_blocks_only_store_preserves_plans(self, tmp_cache):
        """The pre-v2 call signature (blocks mapping alone) must never
        wipe tuned plans -- a block-only re-sweep keeps the plan section."""
        pkey = plan_key("gaussian5", 2, 64, 64)
        store_cache({}, {pkey: PLAN_ENTRY})
        store_cache({TestCache.KEY: TestCache.ENTRY})
        assert load_plans()[pkey] == PLAN_ENTRY
        assert load_cache()[TestCache.KEY] == TestCache.ENTRY

    def test_v1_file_migrates_on_load(self, tmp_cache):
        """Legacy files store the flat block mapping under 'configs'; they
        load as the blocks section with an empty plan section, and the
        next store rewrites them as v2."""
        cache_path().write_text(json.dumps(
            {"meta": {"backend": backend_key(), "version": 1},
             "configs": {TestCache.KEY: TestCache.ENTRY}}))
        invalidate_cache()
        assert load_cache()[TestCache.KEY] == TestCache.ENTRY
        assert load_plans() == {}
        store_cache(load_cache())
        data = json.loads(cache_path().read_text())
        assert data["meta"]["version"] == 2
        assert "configs" not in data
        assert data["blocks"][TestCache.KEY] == TestCache.ENTRY


class TestResolvePlan:
    N, H, W = 2, 64, 64
    KEY = plan_key("gaussian5", 2, 64, 64)

    def _resolve(self, **kw):
        return resolve_plan("gaussian5", self.N, self.H, self.W, 5, 5,
                            separable_ok=True, **kw)

    def test_miss_reproduces_pre_plan_defaults(self, tmp_cache):
        """An untuned shape must change nothing: separable specs default
        to the fused dataflow, everything else defers downstream."""
        assert self._resolve() == PlanConfig("fused", "auto",
                                             None, None, None)
        assert resolve_plan("laplacian", 2, 64, 64, 3, 3,
                            separable_ok=False) == PlanConfig(
                                "direct", "auto", None, None, None)

    def test_cached_plan_wins_on_default_args(self, tmp_cache):
        store_cache({}, {self.KEY: PLAN_ENTRY})
        assert self._resolve() == PlanConfig("two_pass", "kcm", 136, 64,
                                             True)

    def test_explicit_dataflow_rejects_disagreeing_entry(self, tmp_cache):
        store_cache({}, {self.KEY: PLAN_ENTRY})
        # fused=True excludes the cached two_pass winner wholesale
        assert self._resolve(fused=True) == PlanConfig("fused", "auto",
                                                       None, None, None)
        # separable=False likewise
        assert self._resolve(separable=False).dataflow == "direct"

    def test_pinned_mult_impl_keeps_dataflow_drops_blocks(self, tmp_cache):
        """Tuned grid fields were measured under the entry's impl; a
        different pinned impl keeps the dataflow choice but re-defers the
        blocks to the §8 pass-level resolution."""
        store_cache({}, {self.KEY: PLAN_ENTRY})
        assert self._resolve(mult_impl="recurse") == PlanConfig(
            "two_pass", "recurse", None, None, None)

    def test_disagreeing_block_field_drops_entry_blocks(self, tmp_cache):
        store_cache({}, {self.KEY: PLAN_ENTRY})
        got = self._resolve(block_rows=32)
        assert got == PlanConfig("two_pass", "kcm", 32, None, None)

    def test_agreeing_explicit_fields_keep_the_entry(self, tmp_cache):
        store_cache({}, {self.KEY: PLAN_ENTRY})
        assert self._resolve(batch_fold=True) == PlanConfig(
            "two_pass", "kcm", 136, 64, True)

    def test_fully_explicit_fast_path_skips_cache(self, tmp_cache):
        store_cache({}, {self.KEY: PLAN_ENTRY})
        got = self._resolve(fused=True, mult_impl="recurse", block_rows=16,
                            block_cols=32, batch_fold=False)
        assert got == PlanConfig("fused", "recurse", 16, 32, False)

    def test_compiled_pass_drops_cached_kcm_plan(self, tmp_cache):
        """Mosaic cannot lower the KCM gather: a cached 'kcm' plan is
        unusable when the passes compile, so the lookup falls back to the
        pre-plan defaults instead of pinning it."""
        store_cache({}, {self.KEY: PLAN_ENTRY})
        assert self._resolve(interpret=True).mult_impl == "kcm"
        assert self._resolve(interpret=False) == PlanConfig(
            "fused", "auto", None, None, None)

    def test_compiled_filter_plan_pins_recurse(self, tmp_cache):
        from repro.filters import resolve_filter_plan
        store_cache({}, {self.KEY: PLAN_ENTRY})
        plan = resolve_filter_plan("gaussian5", self.N, self.H, self.W,
                                   interpret=False)
        assert plan.mult_impl == "recurse"
        assert plan == PlanConfig("fused", "recurse", *default_blocks(
            "fused", self.N, self.H, self.W, 5, 5, interpret=False)._replace(
                block_cols=self.W))
        assert resolve_filter_plan("gaussian5", self.N, self.H, self.W,
                                   interpret=True).mult_impl == "kcm"


class TestPlanSweep:
    def test_candidates_deterministic_and_concrete(self):
        a = plan_candidates("gaussian5", 2, 64, 64)
        b = plan_candidates("gaussian5", 2, 64, 64)
        assert a == b and len(a) == len(set(a))
        for p in a:
            assert p.dataflow in ("direct", "two_pass", "fused")
            assert p.mult_impl in ("recurse", "kcm")
            assert None not in (p.block_rows, p.block_cols, p.batch_fold)

    def test_non_separable_filter_gets_direct_only(self):
        assert {p.dataflow for p in plan_candidates("laplacian", 2, 64, 64)
                } == {"direct"}

    @staticmethod
    def _fake_timer(winner):
        """Deterministic fake timings: the designated winner is fastest,
        everything else ranks by a stable arbitrary function."""
        def fn(p):
            if p == winner:
                return 10.0
            return 100.0 + (hash(p) % 97)
        return fn

    def test_pruned_sweep_audits_and_keeps_winner(self, tmp_cache):
        cands = plan_candidates("gaussian5", 2, 64, 64)
        # the bound-cheapest candidate as winner: always swept first
        winner = cands[0]
        entry, records = sweep_plan(
            "gaussian5", 2, 64, 64, prune=True,
            measure_fn=self._fake_timer(winner), verbose=False)
        assert entry["candidates"] == len(cands)
        assert entry["swept"] + entry["pruned"] == len(cands)
        assert entry["swept"] == len(records)
        assert entry["pruned"] > 0          # the recurse tail must prune
        assert entry["swept"] < len(cands)  # strictly fewer than exhaustive

    def test_exhaustive_sweep_times_everything(self, tmp_cache):
        cands = plan_candidates("gaussian5", 2, 64, 64)
        entry, records = sweep_plan(
            "gaussian5", 2, 64, 64, prune=False,
            measure_fn=self._fake_timer(cands[0]), verbose=False)
        assert entry["swept"] == len(cands) == len(records)
        assert entry["pruned"] == 0


class TestReproducibility:
    def _stub_timers(self, monkeypatch):
        """Deterministic timings as a pure function of the swept point --
        identical across runs, so any byte diff is the tuner's fault."""
        def measure_stub(kind, cfg, n, h, w, kh, kw, impl, iters=3):
            return float(
                100 + cfg.block_rows % 89 + (cfg.block_cols or 0) % 13
                + cfg.batch_fold + len(kind))

        def measure_plan_stub(name, plan, n, h, w, iters=3):
            return float(
                100 + plan.block_rows % 89 + plan.block_cols % 13
                + bool(plan.batch_fold) + len(plan.dataflow)
                + 900 * (plan.mult_impl == "recurse"))

        monkeypatch.setattr("repro.tuning.autotune.measure", measure_stub)
        monkeypatch.setattr("repro.tuning.autotune.measure_plan",
                            measure_plan_stub)

    def test_two_quick_runs_write_identical_bytes(self, tmp_cache,
                                                  monkeypatch):
        from repro.tuning.autotune import main
        self._stub_timers(monkeypatch)
        monkeypatch.setenv("BENCH_TIMESTAMP", "2026-01-01T00:00:00Z")
        assert main(["--quick", "--no-merge"]) == 0
        first = cache_path().read_bytes()
        assert json.loads(first)["plans"]    # --quick writes plan entries
        invalidate_cache()
        assert main(["--quick", "--no-merge"]) == 0
        assert cache_path().read_bytes() == first


class TestTune:
    def test_tune_records_the_fastest_candidate(self, tmp_cache, monkeypatch):
        """tune() with a stubbed timer must pick the argmin and emit a
        store_cache-ready mapping."""
        fake = {BlockConfig(32, None, False): 30.0,
                BlockConfig(64, None, False): 10.0}

        def measure_stub(kind, cfg, n, h, w, kh, kw, impl, iters=3):
            return fake.get(cfg, 99.0)

        monkeypatch.setattr("repro.tuning.autotune.measure", measure_stub)
        monkeypatch.setattr("repro.tuning.autotune.candidate_blocks",
                            lambda *a: iter(fake))
        sweep = [("direct", 1, 128, 128, 3, 3, "kcm")]
        configs = tune(sweep, verbose=False)
        key = config_key("direct", 1, 128, 128, 3, 3, "kcm")
        assert configs[key]["block_rows"] == 64
        assert configs[key]["us_per_call"] == 10.0
        store_cache(configs)
        assert resolve_blocks("direct", 1, 128, 128, 3, 3,
                              "kcm").block_rows == 64
