"""Block-shape vocabulary and heuristic defaults for the conv grid
(DESIGN.md §8).

A `BlockConfig` names one point of the throughput-first grid organization of
`repro.filters.conv`:

  * `block_rows`  -- height of one output row band (the VMEM tile depth);
  * `block_cols`  -- width of one output column tile, or None for the full
                     image width (no column tiling);
  * `batch_fold`  -- fold the batch into the row axis: each image is given
                     its own kh//2-row zero halo and the padded images are
                     stacked into one tall (1, N*(H+2*ph), W) "image", so
                     the whole batch rides the row-tile grid axis instead of
                     a serial leading batch axis.

`default_blocks` is the cache-miss heuristic; measured winners live in the
per-backend JSON cache (`repro.tuning.cache`, populated by
`repro.tuning.autotune`). On compiled (Mosaic) passes the heuristic's band
height is further capped by a per-step scoped-VMEM budget
(`vmem_step_bytes`); interpreted passes have no such limit.
"""
from __future__ import annotations

from typing import NamedTuple

from repro.core.platform import resolve_interpret

#: block_rows candidates for divisor-based row banding, best (deepest) first.
_BLOCK_ROWS = (128, 64, 32, 16, 8)

#: soft ceiling on a row band's height (keeps the per-step VMEM footprint of
#: a kh-view band stack around a few MiB at typical widths).
MAX_BLOCK_ROWS = 1024

#: scoped VMEM one compiled grid step may plan for: TPU v5e's scoped limit
#: is 16 MiB, the rest is headroom for Mosaic's own scratch.
VMEM_BUDGET_BYTES = 12 << 20

#: live int32 (row, lane) temporaries per output pixel inside one compiled
#: grid step, by dataflow kind. Calibrated on v5e topology compiles of the
#: heaviest tap product, the 16-bit REFMLM recursion of the separable second
#: pass: a direct (5, 1) pass needs ~267 per pixel, the fused 5x5 kernel
#: ~663 (both at 128 lanes); 8-bit passes need far fewer.
_LIVE_TEMPS = {"direct": 270, "fused": 680}


class BlockConfig(NamedTuple):
    """One grid organization of the conv datapath (DESIGN.md §8)."""

    block_rows: int
    block_cols: int | None      # None = full width (no column tiling)
    batch_fold: bool

    def as_dict(self) -> dict:
        return {"block_rows": self.block_rows, "block_cols": self.block_cols,
                "batch_fold": self.batch_fold}


def round_up(x: int, mult: int) -> int:
    return -(-int(x) // mult) * mult


def min_block_rows(kh: int) -> int:
    """Shallowest legal row band: the fused pass stacks kh row-shifted views
    of a 2*(kh//2)-row halo'd band, and sublane tiling wants >= 8."""
    return max(2 * (kh // 2), 8)


def min_block_cols(kw: int) -> int:
    """Narrowest legal column tile: must hold the kw//2-column halo on each
    side (enforced fail-loud for explicit arguments in
    `repro.filters.conv._dispatch`; plan sanitization clamps to it)."""
    return max(2 * (kw // 2), 8)


def choose_block_rows(h: int, max_rows: int = MAX_BLOCK_ROWS) -> int:
    """Largest divisor-candidate band height for an unfolded image of H rows
    that is at most `max_rows` (else the minimum: the pass pads H up to a
    multiple of it)."""
    for br in _BLOCK_ROWS:
        if br <= max_rows and h % br == 0:
            return br
    return _BLOCK_ROWS[-1]


def vmem_step_bytes(kind: str, block_rows: int, w: int, kh: int, kw: int,
                    block_cols: int | None) -> int:
    """Scoped VMEM of one compiled grid step of a conv pass (DESIGN.md §8):
    the input views (kh row-shifted views for 'direct', the two stacked
    band views for 'fused'; x2 when column-tiled), double-buffered, plus
    the double-buffered int32 output block and the in-kernel temporaries.
    Lanes pad to 128; `block_cols=None` is the full width."""
    tiled = block_cols is not None and block_cols < w
    out_cols = round_up(block_cols if tiled else w, 128)
    in_cols = out_cols if tiled else round_up(w + 2 * (kw // 2), 128)
    views = (kh if kind == "direct" else 2) * (2 if tiled else 1)
    per_row = (2 * views * in_cols + 2 * out_cols
               + _LIVE_TEMPS[kind] * out_cols)
    return 4 * block_rows * per_row


def default_blocks(kind: str, n: int, h: int, w: int, kh: int, kw: int, *,
                   batch_fold: bool | None = None,
                   interpret: bool | None = None) -> BlockConfig:
    """Cache-miss heuristic (DESIGN.md §8).

    Small-image batches fold into the row axis (the serial leading batch
    axis is the measured n=8 regression); the folded height is then cut
    into the fewest row bands that stay under `MAX_BLOCK_ROWS`, rounded to
    the sublane multiple of 8. Column tiling only engages on wide images
    where a full-width band would be an oversized VMEM tile. `kind` is the
    dataflow ('direct' | 'fused'); the heuristic is shared between them.
    `batch_fold` forces the fold decision (a caller's explicit choice) so
    the derived band height stays consistent with it -- a serial-batch
    request must get per-image bands, not a fold-sized tall band. On a
    compiled pass (`interpret` resolves False) the band height is also
    capped so one grid step fits `VMEM_BUDGET_BYTES`.
    """
    ph = kh // 2
    bc = None if w <= 512 else 256
    cap = MAX_BLOCK_ROWS
    if not resolve_interpret(interpret):
        fit = VMEM_BUDGET_BYTES // vmem_step_bytes(kind, 1, w, kh, kw, bc)
        cap = min(cap, max(fit // 8 * 8, 8))
    fold = (n > 1 and h <= 256) if batch_fold is None else bool(batch_fold)
    if fold:
        tall = n * (h + 2 * ph)
        steps = max(1, -(-tall // cap))
        br = round_up(-(-tall // steps), 8)
    else:
        br = choose_block_rows(h, cap)
    br = max(br, 2 * ph, 8)
    return BlockConfig(br, bc, fold)


__all__ = ["MAX_BLOCK_ROWS", "VMEM_BUDGET_BYTES", "BlockConfig",
           "choose_block_rows", "default_blocks", "min_block_cols",
           "min_block_rows", "round_up", "vmem_step_bytes"]
