"""Batched multi-filter image pipeline on the REFMLM datapath (DESIGN.md §5).

Layers:
  bank.py     -- the filter definitions (integer taps, fixed-point epilogue,
                 separable decompositions);
  conv.py     -- the batched multiplier-selectable Pallas convolution pass;
  pipeline.py -- user-facing apply_filter / filter_bank_apply (the
                 exec='local'|'sharded'|'streamed' routing, DESIGN.md §9);
  ref.py      -- independently-written pure-jnp oracles for tests.

Scale-out execution (device-mesh sharding, out-of-core tile streaming)
lives in `repro.distribute` and is reached through `apply_filter(...,
exec=...)`.
"""
from repro.filters.bank import (
    FILTER_BANK,
    FILTER_NAMES,
    FilterSpec,
    gaussian_kernel_1d,
    get_filter,
)
from repro.filters.conv import (
    METHODS,
    choose_block_rows,
    conv2d_pass,
    fused_separable_pass,
    tap_multiplier,
)
from repro.filters.pipeline import (
    EXEC_MODES,
    apply_filter,
    apply_filter_batch,
    filter_bank_apply,
    resolve_filter_blocks,
    resolve_filter_plan,
)

__all__ = [
    "EXEC_MODES",
    "FILTER_BANK",
    "FILTER_NAMES",
    "METHODS",
    "FilterSpec",
    "apply_filter",
    "apply_filter_batch",
    "choose_block_rows",
    "conv2d_pass",
    "filter_bank_apply",
    "fused_separable_pass",
    "gaussian_kernel_1d",
    "get_filter",
    "resolve_filter_blocks",
    "resolve_filter_plan",
    "tap_multiplier",
]
