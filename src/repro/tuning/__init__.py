"""Autotuning for the conv datapath (DESIGN.md §8 blocks, §11 plans).

Layers:
  blocks.py   -- `BlockConfig` + the cache-miss heuristic (`default_blocks`);
  plans.py    -- `PlanConfig` (dataflow x blocks) + the plan lookup path
                 (`resolve_plan`: explicit > cached > pre-plan defaults);
  cache.py    -- the committable per-backend JSON cache (blocks + plans
                 sections) and the block lookup path
                 (`resolve_blocks`: explicit > cached > heuristic);
  autotune.py -- the sweeping tuner that populates both sections, with
                 roofline-pruned plan sweeps
                 (`python -m repro.tuning.autotune`).
"""
from repro.tuning.blocks import (
    BlockConfig,
    choose_block_rows,
    default_blocks,
    min_block_cols,
    min_block_rows,
)
from repro.tuning.cache import (
    CACHE_VERSION,
    backend_key,
    cache_generation,
    cache_path,
    config_key,
    invalidate_cache,
    load_cache,
    load_plans,
    resolve_blocks,
    resolve_blocks_cached,
    store_cache,
)
from repro.tuning.plans import (
    DATAFLOWS,
    PlanConfig,
    plan_key,
    resolve_plan,
    sanitize_plan,
)

__all__ = [
    "CACHE_VERSION",
    "DATAFLOWS",
    "BlockConfig",
    "PlanConfig",
    "backend_key",
    "cache_generation",
    "cache_path",
    "choose_block_rows",
    "config_key",
    "default_blocks",
    "invalidate_cache",
    "load_cache",
    "load_plans",
    "min_block_cols",
    "min_block_rows",
    "plan_key",
    "resolve_blocks",
    "resolve_blocks_cached",
    "resolve_plan",
    "sanitize_plan",
    "store_cache",
]
