"""Autotuner for the conv datapath: §8 block sweeps plus the §11 plan
sweeps with roofline pruning (DESIGN.md).

    PYTHONPATH=src python -m repro.tuning.autotune            # bench shapes
    PYTHONPATH=src python -m repro.tuning.autotune --quick    # smoke shapes
    PYTHONPATH=src python -m repro.tuning.autotune --dist     # shard/tile shapes

Two tuned units share the per-backend cache file:

  * **blocks** (§8) -- candidate (block_rows, block_cols, batch_fold) grid
    organizations per (image shape, dataflow), exhaustively
    timed; the pass-level fallback every conv call resolves through.
  * **plans** (§11) -- full `PlanConfig`s (dataflow x blocks) per
    (filter, shape), the pipeline-level choice `apply_filter` resolves on
    default arguments. The plan space is ~3x the block space, so the
    sweep closes the loop with `repro.roofline.conv_model`: candidates are
    enumerated deterministically, sorted by their roofline lower bound,
    and -- once an incumbent is measured -- any candidate whose
    measurement-calibrated bound already exceeds the incumbent (x a safety
    margin) is skipped without timing. Every plan entry records its
    candidates/swept/pruned counts so the pruning is auditable, and
    `scripts/check.sh --smoke-tune` replays the pruned sweep against an
    exhaustive one to prove the winner is never pruned away.

The default sweep covers the shapes the kernel benchmarks and the smoke
guard exercise (128x128 batches at n=1/4/8, 64x64 at n=2/8); `--dist`
sweeps the shard-local band and tile-local batch shapes distributed
execution traces with (DESIGN.md §9 -- the cache keys on what the pass
sees, never the global image shape). The written JSON is committable:
regenerate after kernel changes, commit the diff, and every default
`apply_filter`/`conv2d_pass` call on that backend picks the measured
winners up (explicit arguments always override). Stores MERGE into the
existing per-backend file, so a `--dist` run extends rather than clobbers
the default sweep's winners (`--no-merge` rewrites from scratch).
`generated` stamps honor BENCH_TIMESTAMP, candidate order and tie-breaks
are deterministic, so two runs over identical timings write byte-identical
JSON (asserted in tests/test_tuning.py).
"""
from __future__ import annotations

import argparse
import time
from typing import Callable, Iterable, Iterator

import jax
import numpy as np

from repro.roofline.conv_model import plan_cost
from repro.tuning.blocks import (
    MAX_BLOCK_ROWS,
    BlockConfig,
    choose_block_rows,
    default_blocks,
    round_up,
)
from repro.tuning.cache import (
    backend_key,
    cache_timestamp,
    config_key,
    store_cache,
)
from repro.tuning.plans import PlanConfig, plan_key

#: (kind, n, h, w, kh, kw) rows of the default block sweep.
DEFAULT_SWEEP: tuple[tuple, ...] = tuple(
    (kind, n, h, w, k, k)
    for kind in ("direct", "fused")
    for (n, h, w) in ((1, 128, 128), (4, 128, 128), (8, 128, 128),
                      (2, 64, 64), (8, 64, 64))
    for k in (3, 5)
)
QUICK_SWEEP: tuple[tuple, ...] = tuple(
    (kind, n, 64, 64, 3, 3)
    for kind in ("direct", "fused") for n in (1, 8)
)
#: shard-local band / tile-local batch shapes of distributed execution
#: (DESIGN.md §9): n=32 over 8 batch shards -> (4, H, W) locals; a
#: row-sharded single image -> (1, H/8 + 2*ph, W) bands; the streamed
#: default (256, 256) tile at tile_batch=8 -> (8, 260, 260) for a 5x5.
DIST_SWEEP: tuple[tuple, ...] = tuple(
    (kind, n, h, w, k, k)
    for kind in ("direct", "fused")
    for (n, h, w, k) in ((4, 128, 128, 5), (1, 132, 128, 5), (1, 20, 128, 5),
                         (8, 260, 260, 5), (8, 132, 132, 3))
)

#: (filter, n, h, w) rows of the default plan sweep -- the bench shapes
#: (kernel_bank_* runs gaussian5/gaussian3/sobel_x at n=8 128x128) plus the
#: smoke shapes the check.sh guards time.
PLAN_SWEEP: tuple[tuple[str, int, int, int], ...] = (
    ("gaussian5", 1, 128, 128),
    ("gaussian5", 4, 128, 128),
    ("gaussian5", 8, 128, 128),
    ("gaussian5", 2, 64, 64),
    ("gaussian5", 8, 64, 64),
    ("gaussian3", 4, 128, 128),
    ("gaussian3", 8, 128, 128),
    ("sobel_x", 8, 128, 128),
)
PLAN_QUICK: tuple[tuple[str, int, int, int], ...] = (
    ("gaussian5", 2, 64, 64),
    ("gaussian5", 8, 64, 64),
)

#: pruning safety factor: a candidate is skipped only when its calibrated
#: roofline lower bound exceeds the incumbent's measured time by this much.
#: 2x is deliberately wide slack for the model's halo/fold/launch-floor
#: approximations: the dataflows measure within ~1.6x of each other on the
#: small shapes (where the winner even flips to direct), so every plausible
#: winner is always measured, while the pathological grid shapes still
#: prune wholesale.
PRUNE_MARGIN = 2.0


def candidate_blocks(kind: str, n: int, h: int, w: int, kh: int,
                     kw: int) -> Iterator[BlockConfig]:
    """Valid candidate grid organizations for one shape, deduplicated.

    Row bands: the divisor candidates of the unfolded height, plus -- when
    folding -- single-band and few-band cuts of the folded tall height.
    Column tiles: full width, plus halvings down to 128 on images wide
    enough for a full-width band to be an oversized tile (narrower images
    are covered by the tiling-invariance tests, not the sweep).
    Enumeration order is deterministic (sorted, not set-ordered): the plan
    sweep's byte-reproducibility rides on it.
    """
    ph, pw = kh // 2, kw // 2
    folds = (False,) if n == 1 else (False, True)
    seen = set()
    for fold in folds:
        tall = n * (h + 2 * ph) if fold else h
        rows = {choose_block_rows(h), 32, 64, 128}
        if fold:
            for steps in (1, 2, 4):
                if -(-tall // steps) <= MAX_BLOCK_ROWS * 2:
                    rows.add(round_up(-(-tall // steps), 8))
        cols: set[int | None] = {None}
        bc = w
        while w > 256 and bc // 2 >= max(2 * pw, 128):
            bc //= 2
            cols.add(bc)
        for br in sorted(rows):
            if br < max(2 * ph, 8) or br > 2 * MAX_BLOCK_ROWS:
                continue
            for col in sorted(cols, key=lambda c: -1 if c is None else c):
                cfg = BlockConfig(br, col, fold)
                if cfg not in seen:
                    seen.add(cfg)
                    yield cfg


def _time_us(fn, *args, iters: int = 3, warmup: int = 1) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def measure(kind: str, cfg: BlockConfig, n: int, h: int, w: int, kh: int,
            kw: int, *, iters: int = 3) -> float:
    """Median us/call of one dataflow under one grid organization."""
    # Lazy import: repro.filters.conv imports this package for its defaults.
    from repro.filters.conv import conv2d_pass, fused_separable_pass

    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.integers(0, 256, (n, h, w)), jnp.int32)
    taps1d = np.array([1, 4, 6, 4, 1] if kh == 5 else [4, 8, 4], np.int64)
    kw_common = dict(method="refmlm", block_rows=cfg.block_rows,
                     block_cols=w if cfg.block_cols is None else cfg.block_cols,
                     batch_fold=cfg.batch_fold)
    if kind == "fused":
        fn = lambda x: fused_separable_pass(x, taps1d, taps1d, nbits=8,
                                            nbits2=16, shift=8, post="clip",
                                            **kw_common)
    else:
        taps = np.outer(taps1d, taps1d)
        fn = lambda x: conv2d_pass(x, taps, nbits=8, shift=8, post="clip",
                                   **kw_common)
    return _time_us(fn, imgs, iters=iters)


def tune(sweep: Iterable[tuple] = DEFAULT_SWEEP, *, iters: int = 3,
         verbose: bool = True) -> dict:
    """Sweep every (shape, dataflow) block row and return the winning
    configs as a `store_cache`-ready blocks mapping."""
    configs: dict[str, dict] = {}
    for kind, n, h, w, kh, kw in sweep:
        best: tuple[float, BlockConfig] | None = None
        for cfg in candidate_blocks(kind, n, h, w, kh, kw):
            us = measure(kind, cfg, n, h, w, kh, kw, iters=iters)
            if verbose:
                print(f"# tune {kind} n{n}x{h}x{w} k{kh}x{kw} "
                      f"br={cfg.block_rows} bc={cfg.block_cols} "
                      f"fold={cfg.batch_fold}: {us:.1f}us")
            if best is None or us < best[0]:
                best = (us, cfg)
        assert best is not None
        us, cfg = best
        key = config_key(kind, n, h, w, kh, kw)
        configs[key] = {**cfg.as_dict(), "us_per_call": round(us, 1)}
        # A fold winner that loses to the heuristic default would mean the
        # heuristic is strictly better -- still record the measurement.
        if verbose:
            d = default_blocks(kind, n, h, w, kh, kw)
            print(f"# tune {key}: winner br={cfg.block_rows} "
                  f"bc={cfg.block_cols} fold={cfg.batch_fold} ({us:.1f}us; "
                  f"heuristic was br={d.block_rows} bc={d.block_cols} "
                  f"fold={d.batch_fold})")
    return configs


def plan_candidates(name: str, n: int, h: int, w: int) -> list[PlanConfig]:
    """Deterministic, fully-concrete plan candidates for one (filter, shape).

    Every admissible dataflow of the spec x the §8 block candidates of the
    matching pass kind.
    All fields are concrete (full width spelled `block_cols=w`): tuned
    entries never defer, so a cache hit resolves without any further
    pass-level lookup.
    """
    from repro.filters.bank import get_filter

    spec = get_filter(name)
    kh, kw = spec.ksize
    dataflows = (("fused", "two_pass", "direct") if spec.separable
                 else ("direct",))
    out: list[PlanConfig] = []
    for df in dataflows:
        kind = "fused" if df == "fused" else "direct"
        for cfg in candidate_blocks(kind, n, h, w, kh, kw):
            out.append(PlanConfig(
                df, cfg.block_rows,
                w if cfg.block_cols is None else cfg.block_cols,
                cfg.batch_fold))
    return out


def plan_bound_us(plan: PlanConfig, name: str, n: int, h: int, w: int,
                  backend: str | None = None) -> float:
    """Roofline lower bound of one concrete plan, in us (DESIGN.md §11)."""
    from repro.filters.bank import get_filter

    kh, kw = get_filter(name).ksize
    cost = plan_cost(plan.dataflow, n, h, w, kh, kw,
                     block_rows=plan.block_rows, block_cols=plan.block_cols,
                     batch_fold=bool(plan.batch_fold),
                     backend=backend)
    return cost.lower_bound_s * 1e6


def measure_plan(name: str, plan: PlanConfig, n: int, h: int, w: int, *,
                 iters: int = 3) -> float:
    """Median us/call of one fully-explicit plan through `apply_filter`.

    Every plan field is pinned as an explicit argument, so the measurement
    takes `resolve_plan`'s fully-explicit fast path and is independent of
    whatever the cache currently holds.
    """
    from repro.filters import apply_filter

    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.integers(0, 256, (n, h, w)), jnp.int32)
    kw_plan = dict(method="refmlm", block_rows=plan.block_rows,
                   block_cols=plan.block_cols,
                   batch_fold=bool(plan.batch_fold))
    if plan.dataflow == "direct":
        fn = lambda x: apply_filter(x, name, separable=False, **kw_plan)
    elif plan.dataflow == "two_pass":
        fn = lambda x: apply_filter(x, name, separable=True, fused=False,
                                    **kw_plan)
    else:
        fn = lambda x: apply_filter(x, name, fused=True, **kw_plan)
    return _time_us(fn, imgs, iters=iters)


def sweep_plan(
    name: str,
    n: int,
    h: int,
    w: int,
    *,
    iters: int = 3,
    prune: bool = True,
    margin: float = PRUNE_MARGIN,
    measure_fn: Callable[[PlanConfig], float] | None = None,
    backend: str | None = None,
    verbose: bool = True,
) -> tuple[dict, list[tuple[PlanConfig, float]]]:
    """One (filter, shape) plan sweep -> (cache entry, measured records).

    The closed loop (DESIGN.md §11): candidates sort by roofline lower
    bound (ties broken on the plan tuple -- fully deterministic), and the
    bound-cheapest run first. The model's absolute scale is unknown, so it
    is calibrated online: `scale = min(measured / bound)` over everything
    measured so far maps bounds onto this machine's clock optimistically
    (a truer lower bound than any single ratio). A candidate is pruned
    without timing when `bound * scale > incumbent * margin`. Because
    candidates arrive bound-ascending, pruning is monotone -- once one
    candidate prunes, the rest of the tail prunes too, which is what makes
    the 3x-bigger plan space sweepable.

    `measure_fn` injects the timer (tests replay recorded timings through
    the same loop to prove pruning never discards the exhaustive winner);
    `records` returns every (plan, us) actually measured, for such replays
    and for the audit counters stored in the entry.
    """
    cands = plan_candidates(name, n, h, w)
    bounds = [plan_bound_us(p, name, n, h, w, backend) for p in cands]
    order = sorted(range(len(cands)), key=lambda i: (bounds[i], cands[i]))
    mfn = measure_fn or (
        lambda p: measure_plan(name, p, n, h, w, iters=iters))
    best: tuple[float, PlanConfig] | None = None
    scale: float | None = None
    swept = pruned = 0
    records: list[tuple[PlanConfig, float]] = []
    for i in order:
        plan, bound = cands[i], bounds[i]
        if (prune and best is not None and scale is not None
                and bound * scale > best[0] * margin):
            pruned += 1
            continue
        us = mfn(plan)
        swept += 1
        records.append((plan, us))
        if bound > 0:
            scale = us / bound if scale is None else min(scale, us / bound)
        if verbose:
            print(f"# plan {name} n{n}x{h}x{w} {plan.dataflow} "
                  f"br={plan.block_rows} "
                  f"bc={plan.block_cols} fold={plan.batch_fold}: "
                  f"{us:.1f}us (bound {bound:.1f}us)")
        if best is None or us < best[0]:
            best = (us, plan)
    assert best is not None
    us, plan = best
    entry = {**plan.as_dict(), "us_per_call": round(us, 1),
             "generated": cache_timestamp(), "candidates": len(cands),
             "swept": swept, "pruned": pruned}
    if verbose:
        print(f"# plan {plan_key(name, n, h, w)}: winner {plan.dataflow} "
              f"br={plan.block_rows} bc={plan.block_cols} "
              f"fold={plan.batch_fold} ({us:.1f}us; swept {swept}/"
              f"{len(cands)}, pruned {pruned})")
    return entry, records


def tune_plans(sweep: Iterable[tuple] = PLAN_SWEEP, *, iters: int = 3,
               prune: bool = True, margin: float = PRUNE_MARGIN,
               verbose: bool = True) -> dict:
    """Sweep every (filter, shape) plan row -> `store_cache`-ready plans."""
    plans: dict[str, dict] = {}
    for name, n, h, w in sweep:
        entry, _ = sweep_plan(name, n, h, w, iters=iters, prune=prune,
                              margin=margin, verbose=verbose)
        plans[plan_key(name, n, h, w)] = entry
    return plans


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweep (smoke shapes only)")
    ap.add_argument("--dist", action="store_true",
                    help="sweep the shard/tile-local shapes of distributed "
                         "execution (DESIGN.md §9) instead of the defaults")
    ap.add_argument("--no-merge", action="store_true",
                    help="rewrite the cache from this sweep alone instead of "
                         "merging into the existing per-backend file")
    ap.add_argument("--no-prune", action="store_true",
                    help="exhaustive plan sweep (time every candidate "
                         "instead of roofline-pruning the hopeless tail)")
    ap.add_argument("--prune-margin", type=float, default=PRUNE_MARGIN,
                    help="pruning safety factor over the incumbent's "
                         "measured time (default %(default)s)")
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args(argv)
    sweep = (DIST_SWEEP if args.dist
             else QUICK_SWEEP if args.quick else DEFAULT_SWEEP)
    configs = tune(sweep, iters=args.iters)
    if args.dist:
        # distributed execution re-enters apply_filter with shard-/tile-local
        # shapes; plans for those keys come from the default/quick sweeps of
        # whoever cares -- --dist only extends the block section.
        plans: dict[str, dict] = {}
    else:
        plans = tune_plans(PLAN_QUICK if args.quick else PLAN_SWEEP,
                           iters=args.iters, prune=not args.no_prune,
                           margin=args.prune_margin)
    if not args.no_merge:
        from repro.tuning.cache import load_cache, load_plans
        configs = {**load_cache(), **configs}
        plans = {**load_plans(), **plans}
    path = store_cache(configs, plans)
    print(f"# wrote {path} ({len(configs)} configs, {len(plans)} plans, "
          f"backend={backend_key()})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
