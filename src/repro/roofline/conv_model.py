"""Analytic conv roofline: compute/memory lower bounds for one execution
plan of the filter datapath (DESIGN.md §11).

The §8/§11 autotuner's closed loop needs a *pre-measurement* estimate of a
candidate plan so it can skip candidates whose best possible time already
exceeds the incumbent's measured time. This module derives the two
roofline terms from the conv's own flop/byte accounting rather than a
compiled module (`analysis.analyze_compiled` needs the lowered HLO, which
is exactly the compile the pruner is trying to avoid):

  * **flops** -- 2 (mult+add) per tap product over the padded output grid,
    times `RECURSE_FLOP_FACTOR` for the multiplier's recursion that every
    tap product runs. The direct dataflow pays kh*kw taps per pixel, the
    separable dataflows kh+kw; the *fused* dataflow additionally
    recomputes the horizontal pass on each band's 2*(kh//2) halo rows (the
    VMEM-band price, DESIGN.md §7), which grows as bands shrink.
  * **hbm_bytes** -- int32 reads of the padded input including the halo
    *re*-reads every row band and column tile pays (2*(kh//2) rows per
    band, 2*(kw//2) columns per tile), plus the output write. 'two_pass'
    pays both passes' traffic including the (N, H, W) int32 intermediate's
    full HBM round-trip; 'fused' never materializes it (§7).

`lower_bound_s = max(compute_s, memory_s) + overhead_s` -- the roofline
plus a per-`pallas_call` dispatch floor. The launch term matters: on
small batches the fixed per-call cost dominates the tap work entirely
(measured on CPU interpret: a (2, 64, 64) gaussian5 runs *direct* fastest
-- one launch beats two cheaper passes -- while from (8, 64, 64) up the
two-pass dataflow wins), so a model without it mis-ranks every small
shape. Absolute constants come from presets keyed by device (`hw_for` /
`launch_overhead_for`): 'cpu' for the Pallas interpreter, else the
compiled device's `device_kind`; a compiled device without a preset is an
error, never a silent default. The autotuner calibrates them against its own
measurements (the efficiency scale in `repro.tuning.autotune.sweep_plan`),
so only the *relative* weighting must be roughly right per backend:
interpret-mode CPU is op-dispatch-bound (bytes are nearly free next to
per-element dispatch, so candidates rank by op counts plus launch floors,
and the two-pass HBM round-trip is cheap), while the TPU preset keeps the
published v5e terms where the round-trip is exactly what fusion buys back
and launches are microseconds.
"""
from __future__ import annotations

import dataclasses

import jax

from repro.core.platform import default_interpret
from repro.roofline.analysis import HW

#: conservative flop expansion of one tap product through the REFMLM
#: recursion relative to one multiply-add: the 8-bit kom4 recursion runs
#: up to 16 2x2 base products of several integer ops each, so the factor
#: stays a lower bound.
RECURSE_FLOP_FACTOR = 32.0

#: roofline constants by device key (`device_key`). 'cpu' models the
#: interpret-mode executor: `peak_flops` is the *effective* rate of the
#: modeled flops (recursion factor included) under interpreted Pallas,
#: where the baked recursion is a few whole-array ops a tap (a (2, 64, 64)
#: gaussian5 direct pass measures ~0.7 ms), and the byte term is scaled to
#: be nearly free -- candidates rank by op counts plus launch floors. Compiled devices are
#: keyed by `device_kind`: 'TPU v5 lite' is TPU v5e -- 197 TFLOP/s bf16,
#: 819 GB/s HBM, 1,600 Gbit/s ICI over 4 links (Google Cloud
#: documentation, "TPU v5e"), the `analysis.HW` defaults.
HW_PRESETS: dict[str, HW] = {
    "cpu": HW(peak_flops=7e8 * RECURSE_FLOP_FACTOR, hbm_bw=2e12,
              ici_bw=50e9),
    "TPU v5 lite": HW(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}

#: fixed cost of one kernel launch, by device key and kernel flavor, in
#: seconds. The interpret-mode numbers are deliberately conservative
#: (below the measured per-call floors) but keep the measured ordering:
#: a 1-D or 2-D direct pass dispatches one plain accumulate loop, the
#: fused kernel's band concatenations and dual tap stages cost ~3x that.
LAUNCH_OVERHEAD_S: dict[str, dict[str, float]] = {
    "cpu": {"pass_1d": 100e-6, "pass_2d": 100e-6, "fused": 300e-6},
    "TPU v5 lite": {"pass_1d": 2e-6, "pass_2d": 2e-6, "fused": 2e-6},
}


def device_key() -> str:
    """Preset key of the running backend: 'cpu' when Pallas interprets,
    else the first device's `device_kind`."""
    return "cpu" if default_interpret() else jax.devices()[0].device_kind


def _preset(table: dict, key: str | None):
    key = key or device_key()
    if key not in table:
        raise ValueError(f"no conv roofline preset for device {key!r}; "
                         f"known: {sorted(table)}")
    return table[key]


def hw_for(key: str | None = None) -> HW:
    """Roofline peaks of device `key` (None = the running device)."""
    return _preset(HW_PRESETS, key)


def launch_overhead_for(key: str | None = None) -> dict[str, float]:
    """Per-launch floors of device `key` (None = the running device)."""
    return _preset(LAUNCH_OVERHEAD_S, key)


@dataclasses.dataclass(frozen=True)
class ConvCost:
    """Roofline terms of one plan on one shape (seconds are lower bounds)."""

    flops: float
    hbm_bytes: float
    compute_s: float
    memory_s: float
    overhead_s: float           # fixed per-launch dispatch floor
    lower_bound_s: float        # max(compute, memory) + overhead
    bottleneck: str             # 'compute' | 'memory' | 'dispatch'


def _round_up(x: int, mult: int) -> int:
    return -(-int(x) // mult) * mult


def _pass_terms(n_img: int, rows: int, w: int, kh: int, kw: int, br: int,
                bc: int, *, elem: int = 4) -> tuple[float, float, dict]:
    """(flops, bytes, grid facts) of one conv pass over an (n_img, rows, w)
    input: taps x 2 ops per padded-grid pixel; input read once per tile
    plus the per-band/per-tile halo re-reads; int32 output written once."""
    ph, pw = kh // 2, kw // 2
    br = max(1, min(int(br), _round_up(rows, 8)))
    bc = max(1, min(int(bc), w))
    rows2, w2 = _round_up(rows, br), _round_up(w, bc)
    nbands, ntiles = rows2 // br, w2 // bc
    grid_pix = float(n_img) * rows2 * w2
    flops = 2.0 * kh * kw * grid_pix
    read_rows = rows2 + 2 * ph * nbands
    read_cols = w2 + 2 * pw * ntiles
    bytes_ = elem * float(n_img) * (read_rows * read_cols + rows2 * w2)
    return flops, bytes_, {"nbands": nbands, "ntiles": ntiles,
                           "rows2": rows2, "w2": w2}


def plan_cost(
    dataflow: str,
    n: int,
    h: int,
    w: int,
    kh: int,
    kw: int,
    *,
    block_rows: int,
    block_cols: int | None,
    batch_fold: bool,
    hw: HW | None = None,
    backend: str | None = None,
) -> ConvCost:
    """Roofline lower bound of one `PlanConfig` point (DESIGN.md §11).

    `backend` is a preset key (`device_key`; None = the running device).
    `block_cols=None` means a full-width tile. The fold transform is
    modeled faithfully: a folded batch becomes one (1, N*(H+2*ph), W)
    image whose embedded halo rows are also computed (and cropped), an
    unfolded batch runs N independent (H, W) grids.
    """
    if hw is None:
        hw = hw_for(backend)
    launch = launch_overhead_for(backend)
    ph = kh // 2
    bc = w if block_cols is None else int(block_cols)
    fold = bool(batch_fold) and n > 1

    def img_rows(pass_ph: int) -> tuple[int, int]:
        """(n_img, rows) one pass of `pass_ph` row halo traces with."""
        if fold:
            return 1, n * (h + 2 * pass_ph)
        return n, h

    if dataflow == "direct":
        n_img, rows = img_rows(ph)
        flops, bytes_, _ = _pass_terms(n_img, rows, w, kh, kw,
                                       block_rows, bc)
        overhead_s = launch["pass_2d"]
    elif dataflow == "two_pass":
        n_img, rows = img_rows(0)
        f1, b1, _ = _pass_terms(n_img, rows, w, 1, kw, block_rows, bc)
        n_img, rows = img_rows(ph)
        f2, b2, _ = _pass_terms(n_img, rows, w, kh, 1, block_rows, bc)
        flops, bytes_ = f1 + f2, b1 + b2
        overhead_s = 2 * launch["pass_1d"]
    elif dataflow == "fused":
        n_img, rows = img_rows(ph)
        fv, bytes_, grid = _pass_terms(n_img, rows, w, kh, 1,
                                       block_rows, bc)
        # horizontal pass runs over every band's rows *plus* its 2*ph halo
        # rows (the in-VMEM recompute the fused kernel pays, §7) and over
        # the tile's 2*(kw//2) halo columns.
        h_rows = grid["rows2"] + 2 * ph * grid["nbands"]
        h_cols = grid["w2"] + 2 * (kw // 2) * grid["ntiles"]
        flops = fv + 2.0 * kw * float(n_img) * h_rows * h_cols
        overhead_s = launch["fused"]
    else:
        raise ValueError(f"unknown dataflow {dataflow!r}")

    flops *= RECURSE_FLOP_FACTOR
    compute_s = flops / hw.peak_flops
    memory_s = bytes_ / hw.hbm_bw
    roofline_s = max(compute_s, memory_s)
    bottleneck = ("dispatch" if overhead_s > roofline_s
                  else "compute" if compute_s >= memory_s else "memory")
    return ConvCost(flops=flops, hbm_bytes=bytes_, compute_s=compute_s,
                    memory_s=memory_s, overhead_s=overhead_s,
                    lower_bound_s=roofline_s + overhead_s,
                    bottleneck=bottleneck)


__all__ = ["HW_PRESETS", "LAUNCH_OVERHEAD_S", "RECURSE_FLOP_FACTOR",
           "ConvCost", "device_key", "hw_for", "launch_overhead_for",
           "plan_cost"]
