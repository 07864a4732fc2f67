"""The per-backend tuning cache consulted by the conv datapath: §8 block
winners plus the §11 full execution plans (DESIGN.md).

Format -- one committable JSON file per platform, `blocks_<backend>.json`
next to this module (override the directory with `REPRO_TUNE_CACHE`):

    {
      "meta": {"backend": "cpu", "generated": "<ISO-8601>", "version": 2},
      "blocks": {
        "<kind>/<mult_impl>/n4x128x128/k5x5": {
          "block_rows": 1040, "block_cols": null, "batch_fold": true,
          "us_per_call": 1234.5
        }, ...
      },
      "plans": {
        "gaussian5/n4x128x128": {
          "dataflow": "two_pass", "mult_impl": "kcm", "block_rows": 520,
          "block_cols": 128, "batch_fold": true, "us_per_call": 1234.5,
          "generated": "<ISO-8601>", "candidates": 36, "swept": 14,
          "pruned": 22
        }, ...
      }
    }

Schema v2 (DESIGN.md §11) split the flat v1 `configs` mapping into two
sections. `blocks` keeps the v1 per-pass grid winners under
`config_key(kind, n, h, w, kh, kw, mult_impl)` -- the pass-level dataflow
('direct' | 'fused'; the two-pass separable stages are 'direct' entries
distinguished by their 1-D tap extents), the resolved tap-product
implementation ('kcm' | 'recurse'), the batch/image shape and the filter
extent. `plans` holds the filter-level execution plans under
`repro.tuning.plans.plan_key(filter, n, h, w)`, each entry a full
`PlanConfig` plus its measured time, its own BENCH_TIMESTAMP-honoring
`generated` stamp and the roofline-pruning audit counters
(candidates/swept/pruned) of the sweep that produced it. Legacy v1 files
(`configs` at top level) migrate on load: the old mapping is read as the
`blocks` section and the `plans` section starts empty; the next
`store_cache` writes v2. The multiplier *method* is deliberately in
neither key family: the KCM gather's cost is method-independent and the
tuner sweeps refmlm -- plans and blocks are throughput-only artifacts.

The (n, h, w) in the key is ALWAYS the shape the conv pass itself traces
with. Under distributed execution (`repro.distribute`, DESIGN.md §9) that
is the *shard-local* band shape -- `(N/nb, H/nr + 2*ph, W)`, named by
`repro.distribute.shard_local_shape` -- or the *tile-local* batch shape
`(tile_batch, tile_h + 2*ph, tile_w + 2*pw)` under streaming, never the
global image shape: a winner tuned for the global shape must not be
silently inherited by a shard whose band has a different optimal grid
(asserted in tests/test_distribute.py). `repro.tuning.autotune --dist`
sweeps these shard/tile-local shapes into the cache.

`generated` honors BENCH_TIMESTAMP (like BENCH_kernels.json) and keys are
sorted, so regenerating on a pinned clock is byte-deterministic up to the
measured winners themselves.

`resolve_blocks` is the single lookup path: explicit per-call values win,
then the cache, then the `default_blocks` heuristic.
"""
from __future__ import annotations

import json
import os
import pathlib
import time
from functools import lru_cache

import jax

from repro.tuning.blocks import BlockConfig, default_blocks

CACHE_VERSION = 2


def backend_key() -> str:
    """Platform key for the cache file: the default JAX backend name."""
    return jax.default_backend()


def cache_dir() -> pathlib.Path:
    env = os.environ.get("REPRO_TUNE_CACHE")
    return pathlib.Path(env) if env else pathlib.Path(__file__).parent


def cache_path(backend: str | None = None) -> pathlib.Path:
    return cache_dir() / f"blocks_{backend or backend_key()}.json"


def config_key(kind: str, n: int, h: int, w: int, kh: int, kw: int,
               mult_impl: str) -> str:
    return f"{kind}/{mult_impl}/n{n}x{h}x{w}/k{kh}x{kw}"


def cache_timestamp() -> str:
    """BENCH_TIMESTAMP when set (pinned, reproducible artifacts), else UTC."""
    return os.environ.get("BENCH_TIMESTAMP") or time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime())


@lru_cache(maxsize=None)
def _load(path: str) -> dict:
    """-> {"blocks": {...}, "plans": {...}}, migrating legacy v1 files
    (top-level `configs` = the old flat block mapping, no plans)."""
    empty = {"blocks": {}, "plans": {}}
    p = pathlib.Path(path)
    if not p.exists():
        return empty
    try:
        data = json.loads(p.read_text())
    except (OSError, json.JSONDecodeError):
        return empty
    if not isinstance(data, dict):
        return empty
    if "configs" in data:                       # v1: flat block mapping
        return {"blocks": data.get("configs") or {}, "plans": {}}
    return {"blocks": data.get("blocks") or {},
            "plans": data.get("plans") or {}}


def load_cache(backend: str | None = None) -> dict:
    """Block section: key -> {block_rows, block_cols, batch_fold,
    us_per_call} (v1 files migrate transparently)."""
    return _load(str(cache_path(backend)))["blocks"]


def load_plans(backend: str | None = None) -> dict:
    """Plan section: plan_key -> full PlanConfig entry (DESIGN.md §11);
    empty for legacy v1 files."""
    return _load(str(cache_path(backend)))["plans"]


#: bumped by every invalidate -- downstream memo layers (the serve
#: executor's per-bucket plans) compare it to drop stale resolutions.
_GENERATION = 0


def cache_generation() -> int:
    return _GENERATION


def invalidate_cache() -> None:
    """Drop the in-process caches (after writes, env/backend changes, or in
    tests) -- both the raw file load and the memoised resolutions."""
    global _GENERATION
    _GENERATION += 1
    _load.cache_clear()
    resolve_blocks_cached.cache_clear()


def store_cache(configs: dict, plans: dict | None = None,
                backend: str | None = None) -> pathlib.Path:
    """Write the committable per-backend cache file; returns its path.

    `configs` is the block section; `plans=None` preserves the file's
    existing plan section (so a blocks-only store -- the pre-v2 call
    signature -- never wipes tuned plans), `plans={...}` replaces it.
    Keys in both sections are sorted and `generated` honors
    BENCH_TIMESTAMP, so regeneration is byte-deterministic up to the
    measured winners themselves.
    """
    backend = backend or backend_key()
    path = cache_path(backend)
    if plans is None:
        plans = load_plans(backend)
    payload = {
        "meta": {"backend": backend, "generated": cache_timestamp(),
                 "version": CACHE_VERSION},
        "blocks": {k: configs[k] for k in sorted(configs)},
        "plans": {k: plans[k] for k in sorted(plans)},
    }
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    invalidate_cache()
    return path


def resolve_blocks(
    kind: str,
    n: int,
    h: int,
    w: int,
    kh: int,
    kw: int,
    mult_impl: str,
    *,
    block_rows: int | None = None,
    block_cols: int | None = None,
    batch_fold: bool | None = None,
    interpret: bool | None = None,
) -> BlockConfig:
    """Tuned-cache lookup with explicit-override and heuristic fallback.

    Any explicitly supplied field wins unconditionally. Unset fields come
    from the backend cache only when its entry for this exact
    (kind, shape, mult_impl) AGREES with every explicit field -- a cached
    winner tuned for (say) a folded grid must not donate its fold-sized
    band height to an explicitly unfolded call. On disagreement (or cache
    miss) the `default_blocks` heuristic fills the gaps, with the fold
    decision pinned to the caller's. `block_cols` has no "explicitly full
    width" spelling -- pass `block_cols=w` (a tile as wide as the image
    disables column tiling). `interpret` (None = autodetect) tells the
    heuristic whether the pass compiles, which caps its band height by
    the scoped-VMEM budget.
    """
    if None not in (block_rows, block_cols, batch_fold):
        # fully explicit call: nothing to look up (the serve hot path, which
        # pins a memoised per-bucket resolution on every dispatch,
        # DESIGN.md §10)
        return BlockConfig(int(block_rows), int(block_cols), bool(batch_fold))
    base: BlockConfig | None = None
    entry = load_cache().get(config_key(kind, n, h, w, kh, kw, mult_impl))
    if entry:
        cached = BlockConfig(entry["block_rows"], entry["block_cols"],
                             bool(entry["batch_fold"]))
        if ((block_rows is None or int(block_rows) == cached.block_rows)
                and (block_cols is None or block_cols == cached.block_cols)
                and (batch_fold is None
                     or bool(batch_fold) == cached.batch_fold)):
            base = cached
    if base is None:
        base = default_blocks(kind, n, h, w, kh, kw, batch_fold=batch_fold,
                              interpret=interpret)
    return BlockConfig(
        base.block_rows if block_rows is None else int(block_rows),
        base.block_cols if block_cols is None else int(block_cols),
        base.batch_fold if batch_fold is None else bool(batch_fold),
    )


@lru_cache(maxsize=None)
def resolve_blocks_cached(kind: str, n: int, h: int, w: int, kh: int,
                          kw: int, mult_impl: str,
                          interpret: bool | None = None) -> BlockConfig:
    """Memoised default-field `resolve_blocks` for steady-state dispatch.

    The serving layer (and any other hot loop re-resolving the same
    (kind, shape, mult_impl) point) pays the JSON-dict lookup and key
    formatting once; later calls are one dict hit on the memo.
    `invalidate_cache()` clears this memo together with the file cache, so
    a `store_cache` write is still visible process-wide. Explicit
    per-call overrides have no business here -- they bypass the cache
    entirely via `resolve_blocks`' fully-explicit fast path.
    """
    return resolve_blocks(kind, n, h, w, kh, kw, mult_impl,
                          interpret=interpret)


__all__ = ["CACHE_VERSION", "backend_key", "cache_generation", "cache_path",
           "config_key", "invalidate_cache", "load_cache", "load_plans",
           "resolve_blocks", "resolve_blocks_cached", "store_cache"]
