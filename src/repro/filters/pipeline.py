"""Batched multi-filter image pipeline over the REFMLM datapath
(DESIGN.md §5).

    apply_filter(imgs, "sobel_x", method="refmlm")        one filter
    filter_bank_apply(imgs, method="refmlm")              the whole bank

Accepts a single (H, W) image or an (N, H, W) batch (NHWC with a trailing
unit channel axis is also accepted and squeezed -- the datapath is
grayscale, like the paper's fingerprint experiment). The direct-vs-separable
dataflow choice is handled here; tile padding and the grid organization
(row bands x column tiles, batch fold) live in the conv passes, defaulted
from the per-backend autotune cache (DESIGN.md §8).

Execution modes (DESIGN.md §9): `exec='local'` is the single-device path;
`exec='sharded'` runs the same pass under `shard_map` over a (batch, rows)
device mesh with halo-exchanged row bands; `exec='streamed'` walks an
out-of-core source in overlapping tiles. Both scale-out modes live in
`repro.distribute` and are bit-identical to local by construction.
"""
from __future__ import annotations

from functools import partial

import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.core.platform import resolve_interpret
from repro.filters.bank import (
    FILTER_NAMES,
    FilterSpec,
    get_filter,
    max_intermediate,
)
from repro.filters.conv import (
    conv2d_pass,
    fused_separable_pass,
    second_pass_nbits,
)
from repro.tuning.plans import PlanConfig, resolve_plan


def _normalize(imgs: Array) -> tuple[Array, tuple[int, ...]]:
    """-> ((N, H, W) int32, original shape). Accepts (H,W)/(N,H,W)/(N,H,W,1)."""
    orig = imgs.shape
    if imgs.ndim == 4:
        if orig[-1] != 1:
            raise ValueError(f"NHWC input must have C=1, got {orig}")
        imgs = imgs[..., 0]
    elif imgs.ndim == 2:
        imgs = imgs[None]
    elif imgs.ndim != 3:
        raise ValueError(f"expected (H,W), (N,H,W) or (N,H,W,1), got {orig}")
    return imgs.astype(jnp.int32), orig


def _restore(out: Array, orig: tuple[int, ...]) -> Array:
    if len(orig) == 4:
        return out[..., None]
    if len(orig) == 2:
        return out[0]
    return out


def _apply(imgs: Array, spec: FilterSpec, method: str, nbits: int,
           separable: bool, fused: bool, mult_impl: str,
           block_rows: int | None, block_cols: int | None,
           batch_fold: bool | None, interpret: bool | None) -> Array:
    blocks = dict(block_rows=block_rows, block_cols=block_cols,
                  batch_fold=batch_fold)
    if separable:
        nb2 = second_pass_nbits(max_intermediate(spec),
                                int(np.abs(spec.sep_col).max()))
        if fused:
            out = fused_separable_pass(
                imgs, spec.sep_row, spec.sep_col, method=method,
                nbits=nbits, nbits2=nb2, shift=spec.shift, post=spec.post,
                interpret=interpret, mult_impl=mult_impl, **blocks)
        else:
            run = partial(conv2d_pass, interpret=interpret,
                          mult_impl=mult_impl, **blocks)
            # keep the taps host-side NumPy: under a trace (shard_map in the
            # distributed path, DESIGN.md §9) a jnp constant would become a
            # tracer and defeat the KCM staticness check
            row = np.asarray(spec.sep_row, np.int32)[None, :]    # (1, kw)
            col = np.asarray(spec.sep_col, np.int32)[:, None]    # (kh, 1)
            tmp = run(imgs, row, method=method, nbits=nbits, shift=0,
                      post="none")
            out = run(tmp, col, method=method, nbits=nb2, shift=spec.shift,
                      post=spec.post)
    else:
        out = conv2d_pass(imgs, np.asarray(spec.taps, np.int32),
                          method=method, nbits=nbits, shift=spec.shift,
                          post=spec.post, interpret=interpret,
                          mult_impl=mult_impl, **blocks)
    return out.astype(jnp.uint8)


EXEC_MODES = ("local", "sharded", "streamed")


def apply_filter(
    imgs: Array,
    filt: FilterSpec | str,
    *,
    method: str = "refmlm",
    nbits: int = 8,
    separable: bool | None = None,
    fused: bool | None = None,
    mult_impl: str = "auto",
    block_rows: int | None = None,
    block_cols: int | None = None,
    batch_fold: bool | None = None,
    interpret: bool | None = None,
    exec: str = "local",
    devices: int | None = None,
    mesh_shape: tuple[int, int] | None = None,
    halo: str = "exchange",
    tile: tuple[int, int] | None = None,
    tile_batch: int = 8,
    out=None,
    journal=None,
    resume: bool = False,
):
    """Run one bank filter over an image batch through the selected multiplier.

    The execution plan -- dataflow, tap-product implementation and grid
    organization -- resolves through the per-backend plan cache
    (DESIGN.md §11): on default arguments a tuned `PlanConfig` for this
    (filter, batch/image shape) wins, and a cache miss reproduces the
    fixed pre-plan defaults. Explicit arguments always override.
    `separable=False` forces the direct KxK window (bit-identical for
    exact multipliers -- asserted in tests); `separable=True` admits only
    the two 1-D pass dataflows. Of those, fused=True runs both passes in
    one kernel (DESIGN.md §7) and fused=False forces the two-kernel
    dataflow with its HBM intermediate (the before/after benchmark axis).
    mult_impl pins the tap-product implementation ('recurse' | 'kcm';
    'auto' defers to the plan, then to the pass-level resolution --
    see repro.filters.conv); interpret=None autodetects the backend. The
    grid organization (block_rows, block_cols, batch_fold) defaults
    through the plan, then the §8 block cache -- outputs are bit-identical
    across every plan (asserted in tests/test_plan_equivalence.py), so all
    of these are pure throughput knobs.

    `exec` selects the execution mode (DESIGN.md §9): 'local' (default)
    runs on one device and returns a jax Array; 'sharded' distributes over
    a (batch, rows) device mesh (`devices` / `mesh_shape` size it, `halo`
    picks 'exchange' ppermute neighbor exchange or 'embedded' overlapping
    host windows); 'streamed' walks the source out-of-core in overlapping
    `tile`-shaped batches of `tile_batch` and returns a NumPy array
    (writing into `out` -- an ndarray or memmap -- when given; `journal` /
    `resume` are the §12 crash-resume surface: completed tiles journal
    beside an `out` memmap and `resume=True` skips them bit-identically).
    All three modes are bit-identical (asserted in
    tests/test_distribute.py).
    """
    if exec not in EXEC_MODES:
        raise ValueError(f"exec must be one of {EXEC_MODES}, got {exec!r}")
    filter_kw = dict(method=method, nbits=nbits, separable=separable,
                     fused=fused, mult_impl=mult_impl, block_rows=block_rows,
                     block_cols=block_cols, batch_fold=batch_fold,
                     interpret=interpret)
    if exec == "sharded":
        from repro.distribute import sharded_apply_filter
        if (tile is not None or out is not None or tile_batch != 8
                or journal is not None or resume):
            raise ValueError("tile/tile_batch/out/journal/resume are "
                             "streamed-mode arguments")
        return sharded_apply_filter(imgs, filt, devices=devices,
                                    mesh_shape=mesh_shape, halo=halo,
                                    **filter_kw)
    if exec == "streamed":
        from repro.distribute import stream_filter
        if devices is not None or mesh_shape is not None or halo != "exchange":
            raise ValueError("devices/mesh_shape/halo are sharded-mode "
                             "arguments")
        return stream_filter(np.asarray(imgs), filt,
                             tile=tile if tile is not None else (256, 256),
                             tile_batch=tile_batch, out=out, journal=journal,
                             resume=resume, **filter_kw)
    if ((devices, mesh_shape, tile, out, journal) != (None,) * 5
            or halo != "exchange" or tile_batch != 8 or resume):
        raise ValueError("devices/mesh_shape/halo/tile/tile_batch/out/"
                         "journal/resume require exec='sharded' or "
                         "exec='streamed'")
    spec = get_filter(filt) if isinstance(filt, str) else filt
    if separable and not spec.separable:
        raise ValueError(f"filter {spec.name!r} has no separable decomposition")
    if fused and (separable is False or not spec.separable):
        raise ValueError("fused=True requires the separable dataflow")
    arr, orig = _normalize(imgs)
    n, h, w = arr.shape
    kh, kw = spec.ksize
    plan = resolve_plan(spec.name, n, h, w, kh, kw,
                        separable_ok=spec.separable, mult_impl=mult_impl,
                        separable=separable, fused=fused,
                        block_rows=block_rows, block_cols=block_cols,
                        batch_fold=batch_fold, interpret=interpret)
    out = _apply(arr, spec, method, nbits, plan.dataflow != "direct",
                 plan.dataflow == "fused", plan.mult_impl, plan.block_rows,
                 plan.block_cols, plan.batch_fold, interpret)
    return _restore(out, orig)


def resolve_filter_blocks(
    filt: FilterSpec | str,
    n: int,
    h: int,
    w: int,
    *,
    method: str = "refmlm",
    mult_impl: str = "auto",
    separable: bool | None = None,
    fused: bool | None = None,
    interpret: bool | None = None,
) -> "BlockConfig":
    """The grid organization `apply_filter` would resolve for an (n, h, w)
    batch of `filt` -- dataflow kind, tap extents and resolved mult_impl
    included, one `repro.tuning.resolve_blocks` consult total.

    This is the serving layer's per-bucket memoisation hook (DESIGN.md
    §10): resolve once per (bucket, coalesced batch size), then pin the
    fields explicitly on every `apply_filter` dispatch so the steady-state
    hot path does no cache re-resolution (explicit values win and
    short-circuit the lookup). Outputs are bit-identical across grid
    organizations (§8), so pinning is throughput-only. Note `block_cols`
    is returned in the cache's vocabulary: None means full width, which
    pins explicitly as `block_cols=w`.
    """
    from repro.filters.conv import _resolve_mult_impl
    from repro.tuning import resolve_blocks_cached

    interpret = resolve_interpret(interpret)
    spec = get_filter(filt) if isinstance(filt, str) else filt
    separable = spec.separable if separable is None else separable
    fused = separable if fused is None else fused
    if fused and separable:
        kind = "fused"
        kh, kw = len(spec.sep_col), len(spec.sep_row)
        tap_arrays = (spec.sep_row, spec.sep_col)
    else:
        kind = "direct"
        kh, kw = np.shape(spec.taps)
        tap_arrays = (spec.taps,)
    impl = _resolve_mult_impl(mult_impl, *tap_arrays, interpret=interpret)
    return resolve_blocks_cached(kind, n, h, w, kh, kw, impl, interpret)


def resolve_filter_plan(
    filt: FilterSpec | str,
    n: int,
    h: int,
    w: int,
    *,
    method: str = "refmlm",
    mult_impl: str = "auto",
    separable: bool | None = None,
    fused: bool | None = None,
    interpret: bool | None = None,
) -> PlanConfig:
    """The fully-concrete execution plan `apply_filter` would run for an
    (n, h, w) batch of `filt`: dataflow, resolved mult_impl and grid
    organization, one plan-cache consult total (DESIGN.md §11).

    This is the serving layer's per-bucket memoisation hook (DESIGN.md
    §10): resolve once per (bucket, coalesced batch size), then pin every
    field explicitly on each `apply_filter` dispatch so the steady-state
    hot path takes `resolve_plan`'s fully-explicit fast path and does no
    cache re-resolution. Fields the plan defers (an untuned shape) are
    concretized here -- mult_impl through the pass-level staticness
    resolution, blocks through the §8 block cache of the matching pass
    kind (a full-width tile pins explicitly as `block_cols=w`). Outputs
    are bit-identical across plans, so pinning is throughput-only.
    """
    from repro.filters.conv import _resolve_mult_impl
    from repro.tuning import resolve_blocks_cached

    interpret = resolve_interpret(interpret)
    spec = get_filter(filt) if isinstance(filt, str) else filt
    plan = resolve_plan(spec.name, n, h, w, *spec.ksize,
                        separable_ok=spec.separable, mult_impl=mult_impl,
                        separable=separable, fused=fused, interpret=interpret)
    if plan.dataflow == "fused":
        kind = "fused"
        kh, kw = len(spec.sep_col), len(spec.sep_row)
        tap_arrays = (spec.sep_row, spec.sep_col)
    elif plan.dataflow == "two_pass":
        # the second (column) pass carries the row halo; its §8 entry sizes
        # the pinned grid when the plan defers
        kind = "direct"
        kh, kw = len(spec.sep_col), 1
        tap_arrays = (spec.sep_row, spec.sep_col)
    else:
        kind = "direct"
        kh, kw = spec.ksize
        tap_arrays = (spec.taps,)
    impl = _resolve_mult_impl(plan.mult_impl, *tap_arrays,
                              interpret=interpret)
    if None in (plan.block_rows, plan.block_cols, plan.batch_fold):
        base = resolve_blocks_cached(kind, n, h, w, kh, kw, impl, interpret)
        plan = PlanConfig(
            plan.dataflow, impl,
            base.block_rows if plan.block_rows is None else plan.block_rows,
            (plan.block_cols if plan.block_cols is not None
             else w if base.block_cols is None else base.block_cols),
            base.batch_fold if plan.batch_fold is None else plan.batch_fold)
    else:
        plan = plan._replace(mult_impl=impl)
    return plan


def apply_filter_batch(
    imgs: "list[np.ndarray]",
    filt: FilterSpec | str,
    *,
    pad_to: int | None = None,
    **kw,
) -> "list[np.ndarray]":
    """Coalesce same-shape single images into one (N, H, W) `apply_filter`
    call and split the output back per image -- the serving layer's batch
    merge/split hook (DESIGN.md §10).

    `pad_to` zero-pads the batch axis up to a fixed traced size (the
    serve executor's power-of-two batch rounding, which bounds the number
    of compiled executables per bucket); pad images are dropped from the
    returned list. Each returned output is bit-identical to the
    single-image `apply_filter` call -- the §8 batch fold embeds every
    image's own zero halo, so batch neighbors (and zero pads) can never
    leak into a request's pixels (asserted in tests/test_serve.py).
    """
    if not imgs:
        return []
    shape = np.shape(imgs[0])
    for im in imgs[1:]:
        if np.shape(im) != shape:
            raise ValueError(f"apply_filter_batch needs uniform shapes; got "
                             f"{np.shape(im)} alongside {shape}")
    if len(shape) != 2:
        raise ValueError(f"expected (H, W) images, got shape {shape}")
    n = len(imgs)
    batch = np.stack([np.asarray(im) for im in imgs]).astype(np.int32)
    if pad_to is not None and pad_to > n:
        batch = np.concatenate(
            [batch, np.zeros((pad_to - n, *shape), np.int32)])
    out = np.asarray(apply_filter(batch, filt, **kw))
    return [out[i] for i in range(n)]


def filter_bank_apply(
    imgs: Array,
    filters: tuple[str, ...] | None = None,
    *,
    method: str = "refmlm",
    **kw,
) -> dict[str, Array]:
    """Run many filters over one batch: name -> uint8 output batch."""
    names = FILTER_NAMES if filters is None else tuple(filters)
    return {name: apply_filter(imgs, name, method=method, **kw)
            for name in names}


__all__ = ["EXEC_MODES", "apply_filter", "apply_filter_batch",
           "filter_bank_apply", "resolve_filter_blocks",
           "resolve_filter_plan"]
