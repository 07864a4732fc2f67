"""Full execution plans for the filter datapath (DESIGN.md §11).

A `PlanConfig` names everything the tuner may choose for one
(filter, batch/image shape) point -- not just the §8 grid organization but
the *dataflow*:

  * `dataflow`   -- 'direct' (one KxK pass), 'two_pass' (separable row then
                    column kernels with an HBM int32 intermediate), or
                    'fused' (both 1-D passes in one kernel, the intermediate
                    held in a VMEM halo band, DESIGN.md §7);
  * `block_rows` / `block_cols` / `batch_fold` -- the §8 grid fields; None
                    means "defer to the pass-level block cache/heuristic".

Tuned plan entries (the `plans` section of the cache,
`repro.tuning.cache`) are always fully concrete; the deferring spellings
exist so an *untuned* resolution changes nothing about the pre-plan
behavior -- on a cache miss `resolve_plan` reproduces exactly the fixed
default the pipeline used before plans existed (separable specs run
fused).

Every plan is a pure throughput choice: outputs are bit-identical across
dataflows (the separability contract, DESIGN.md §5) and grid
organizations (§8), so a wrong -- even adversarially poisoned --
cache entry can only ever cost time, never bytes
(tests/test_plan_equivalence.py). `sanitize_plan` enforces that by
clamping cached fields to the kernel floors (`min_block_rows` /
`min_block_cols`) instead of letting a poisoned entry trip the
explicit-argument fail-loud checks in `repro.filters.conv`, and by
rejecting entries whose dataflow the filter cannot run.
"""
from __future__ import annotations

from typing import NamedTuple

from repro.tuning.blocks import min_block_cols, min_block_rows, round_up
from repro.tuning.cache import load_plans

#: dataflow vocabulary of the plan search space (DESIGN.md §11).
DATAFLOWS = ("direct", "two_pass", "fused")


class PlanConfig(NamedTuple):
    """One full execution plan of the filter datapath (DESIGN.md §11)."""

    dataflow: str               # 'direct' | 'two_pass' | 'fused'
    block_rows: int | None      # None = defer to pass-level resolution
    block_cols: int | None      # None = defer (tuned entries store ints;
                                # a full-width tile is spelled block_cols=w)
    batch_fold: bool | None     # None = defer

    def as_dict(self) -> dict:
        return {"dataflow": self.dataflow, "block_rows": self.block_rows, "block_cols": self.block_cols,
                "batch_fold": self.batch_fold}


def plan_key(name: str, n: int, h: int, w: int) -> str:
    """Plan-cache key: filter name x the (n, h, w) the pipeline traces with
    (shard-/tile-local under distributed execution, DESIGN.md §9 doctrine).
    The multiplier *method* is deliberately not in the key, like the §8
    block keys: plans are throughput-only and the tuner sweeps refmlm."""
    return f"{name}/n{n}x{h}x{w}"


def allowed_dataflows(separable_ok: bool, separable: bool | None,
                      fused: bool | None) -> tuple[str, ...]:
    """Dataflows the caller's explicit `separable=`/`fused=` arguments
    admit, most-preferred first (the head is the cache-miss default and
    reproduces the pre-plan fixed choice). Argument *validation* (e.g.
    separable=True on a non-separable spec) stays in the pipeline -- this
    only narrows the plan search."""
    if not separable_ok or separable is False:
        return ("direct",)
    if fused is True:
        return ("fused",)
    if fused is False:
        return ("two_pass",)
    if separable is True:
        return ("fused", "two_pass")
    return ("fused", "two_pass", "direct")


def sanitize_plan(plan: PlanConfig, n: int, h: int, w: int, kh: int,
                  kw: int) -> PlanConfig | None:
    """Clamp a cache-sourced plan to the kernel floors; None if unusable.

    Cached fields are *not* explicit caller arguments, so they must never
    trip the fail-loud explicit checks in `repro.filters.conv` -- a
    poisoned entry degrades to a slower valid plan instead of an error:
    block_rows floors at the fused pass's 2*(kh//2) halo depth and ceils at
    one band over the (folded) height (an absurd tall band would otherwise
    pad the whole image up to it); block_cols floors at the column-halo
    minimum, and any tile at least as wide as the image means full width.
    """
    if plan.dataflow not in DATAFLOWS:
        return None
    ph = kh // 2
    br, bc, fold = plan.block_rows, plan.block_cols, plan.batch_fold
    fold = None if fold is None else bool(fold)
    if br is not None:
        tall = n * (h + 2 * ph) if fold else h
        br = min(max(int(br), min_block_rows(kh)), round_up(tall, 8))
    if bc is not None:
        bc = min(int(bc), w)
        if bc < w:
            bc = max(bc, min_block_cols(kw))
    return plan._replace(block_rows=br, block_cols=bc, batch_fold=fold)


def _entry_plan(entry: dict) -> PlanConfig | None:
    """A cache entry's PlanConfig, or None when the entry is malformed."""
    try:
        return PlanConfig(str(entry["dataflow"]), int(entry["block_rows"]),
                          int(entry["block_cols"]),
                          bool(entry["batch_fold"]))
    except (KeyError, TypeError, ValueError):
        return None


def resolve_plan(
    name: str,
    n: int,
    h: int,
    w: int,
    kh: int,
    kw: int,
    *,
    separable_ok: bool,
    separable: bool | None = None,
    fused: bool | None = None,
    block_rows: int | None = None,
    block_cols: int | None = None,
    batch_fold: bool | None = None,
) -> PlanConfig:
    """The single plan lookup path: explicit > cached > pre-plan defaults.

    Field-wise precedence mirrors §8's `resolve_blocks` doctrine:

      * every explicitly supplied argument wins unconditionally;
      * the cached plan donates its remaining fields only where it AGREES
        with the explicit ones -- a dataflow the caller's `separable=` /
        `fused=` arguments exclude rejects the entry wholesale, and any
        disagreeing explicit block field drops the entry's block fields as
        a unit;
      * what remains unset defers downstream: dataflow to the pre-plan
        fixed default (fused when the spec separates, else direct), block
        fields to the §8 block cache/heuristic inside the conv passes.
    """
    allowed = allowed_dataflows(separable_ok, separable, fused)
    if (len(allowed) == 1
            and None not in (block_rows, block_cols, batch_fold)):
        # fully explicit call: nothing to look up (the serve hot path, which
        # pins a memoised per-bucket plan on every dispatch, DESIGN.md §10)
        return PlanConfig(allowed[0], int(block_rows), int(block_cols),
                          bool(batch_fold))
    cand: PlanConfig | None = None
    entry = load_plans().get(plan_key(name, n, h, w))
    if entry:
        cand = _entry_plan(entry)
        if cand is not None:
            cand = sanitize_plan(cand, n, h, w, kh, kw)
        if cand is not None and cand.dataflow not in allowed:
            cand = None
        if cand is not None and any(
            exp is not None and exp != got
            for exp, got in ((block_rows, cand.block_rows),
                             (block_cols, cand.block_cols),
                             (None if batch_fold is None
                              else bool(batch_fold), cand.batch_fold))
        ):
            cand = cand._replace(block_rows=None, block_cols=None,
                                 batch_fold=None)
    if cand is None:
        cand = PlanConfig(allowed[0], None, None, None)
    return PlanConfig(
        cand.dataflow,
        cand.block_rows if block_rows is None else int(block_rows),
        cand.block_cols if block_cols is None else int(block_cols),
        cand.batch_fold if batch_fold is None else bool(batch_fold),
    )


__all__ = ["DATAFLOWS", "PlanConfig", "allowed_dataflows",
           "plan_key", "resolve_plan", "sanitize_plan"]
