"""Batched multi-filter image pipeline over the REFMLM datapath
(DESIGN.md §5).

    apply_filter(imgs, "sobel_x", method="refmlm")        one filter
    filter_bank_apply(imgs, method="refmlm")              the whole bank

Accepts a single (H, W) image or an (N, H, W) batch (NHWC with a trailing
unit channel axis is also accepted and squeezed -- the datapath is
grayscale, like the paper's fingerprint experiment). Pixels come in a
container (`CONTAINERS`, DESIGN.md §5): uint16 images are answered in
uint16, clipped to 65535, with the separable column pass sized for their
row sums (the 32-bit REFMLM); every other dtype keeps the 8-bit contract,
answered in uint8 and clipped to 255. The direct-vs-separable
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

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.core.platform import resolve_interpret
from repro.core.taps import method_max_nbits
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
from repro.obs import trace as obs_trace
from repro.tuning.plans import PlanConfig, resolve_plan

#: the dtype `apply_filter_batch` stacks a batch in for the device when
#: its pixels are not all in one container (`batch_dtype`).
BATCH_DTYPE = np.int32

#: the pixel containers, by name: a request is answered in its own.
CONTAINERS = {"uint8": np.uint8, "uint16": np.uint16}

_NARROW = tuple(np.dtype(d) for d in CONTAINERS.values())


def container_of(img) -> str:
    """The container `img` is answered in: 'uint16' for uint16 pixels,
    'uint8' for any other dtype (the 8-bit contract)."""
    dtype = np.dtype(getattr(img, "dtype", np.uint8))
    return "uint16" if dtype == np.uint16 else "uint8"


def container_top(container: str) -> int:
    """The largest pixel of `container`: the clip of every answer."""
    return int(np.iinfo(CONTAINERS[container]).max)


def _container(container, imgs) -> str:
    """An explicit container's name, checked; None -> `container_of`."""
    if container is None:
        return container_of(imgs)
    name = np.dtype(container).name
    if name not in CONTAINERS:
        raise ValueError(f"container must be one of {tuple(CONTAINERS)}, "
                         f"got {name!r}")
    return name


def filter_nbits(spec: FilterSpec, nbits: int, container: str) -> tuple:
    """The multiplier widths `spec` runs at on `container` pixels: (nbits,)
    for a direct filter; with the separable column pass's width appended,
    sized from the container's top (`second_pass_nbits`)."""
    if not spec.separable:
        return (int(nbits),)
    return (int(nbits),
            second_pass_nbits(max_intermediate(spec, container_top(container)),
                              int(np.abs(spec.sep_col).max())))


def check_method_nbits(method: str, widths) -> None:
    """ValueError, naming the method and the width, where `method` has no
    multiplier as wide as the widest of `widths` (`method_max_nbits`)."""
    widest, top = max(widths), method_max_nbits(method)
    if top is not None and widest > top:
        raise ValueError(f"method {method!r} has no {widest}-bit multiplier "
                         f"(its widest is {top} bits)")


def batch_dtype(imgs) -> np.dtype:
    """The dtype `apply_filter_batch` sends `imgs` to the device in: their
    container when every image is an ndarray of one container's dtype
    (uint8 or uint16; the jitted conv passes widen it to int32 on the
    device), else `BATCH_DTYPE`, so an int32 pixel above 255 never wraps."""
    dtype = getattr(imgs[0], "dtype", None)
    if dtype in _NARROW and all(isinstance(im, np.ndarray)
                                and im.dtype == dtype for im in imgs):
        return np.dtype(dtype)
    return np.dtype(BATCH_DTYPE)


def _normalize(imgs: Array) -> tuple[Array, tuple[int, ...]]:
    """-> ((N, H, W), original shape). Accepts (H,W)/(N,H,W)/(N,H,W,1).
    uint8 and uint16 pixels keep their dtype, which the jitted conv passes
    widen to int32 inside the jit; any other dtype is cast to int32."""
    orig = imgs.shape
    if imgs.ndim == 4:
        if orig[-1] != 1:
            raise ValueError(f"NHWC input must have C=1, got {orig}")
        imgs = imgs[..., 0]
    elif imgs.ndim == 2:
        imgs = imgs[None]
    elif imgs.ndim != 3:
        raise ValueError(f"expected (H,W), (N,H,W) or (N,H,W,1), got {orig}")
    if np.dtype(imgs.dtype) in _NARROW:
        return imgs, orig
    return imgs.astype(jnp.int32), orig


def _restore(out: Array, orig: tuple[int, ...]) -> Array:
    if len(orig) == 4:
        return out[..., None]
    if len(orig) == 2:
        return out[0]
    return out


def _apply(imgs: Array, spec: FilterSpec, method: str, nbits: int,
           separable: bool, fused: bool, block_rows: int | None, block_cols: int | None,
           batch_fold: bool | None, interpret: bool | None,
           container: str) -> Array:
    blocks = dict(block_rows=block_rows, block_cols=block_cols,
                  batch_fold=batch_fold)
    top = container_top(container)
    if separable:
        nb2 = filter_nbits(spec, nbits, container)[1]
        if fused:
            out = fused_separable_pass(
                imgs, spec.sep_row, spec.sep_col, method=method,
                nbits=nbits, nbits2=nb2, shift=spec.shift, post=spec.post,
                top=top, interpret=interpret, **blocks)
        else:
            run = partial(conv2d_pass, interpret=interpret, **blocks)
            # keep the taps host-side NumPy: under a trace (shard_map in the
            # distributed path, DESIGN.md §9) a jnp constant would become a
            # tracer, and the pass could no longer bake them
            row = np.asarray(spec.sep_row, np.int32)[None, :]    # (1, kw)
            col = np.asarray(spec.sep_col, np.int32)[:, None]    # (kh, 1)
            tmp = run(imgs, row, method=method, nbits=nbits, shift=0,
                      post="none")
            out = run(tmp, col, method=method, nbits=nb2, shift=spec.shift,
                      post=spec.post, top=top)
    else:
        out = conv2d_pass(imgs, np.asarray(spec.taps, np.int32),
                          method=method, nbits=nbits, shift=spec.shift,
                          post=spec.post, top=top, interpret=interpret,
                          **blocks)
    return out.astype(CONTAINERS[container])


EXEC_MODES = ("local", "sharded", "streamed")


def apply_filter(
    imgs: Array,
    filt: FilterSpec | str,
    *,
    method: str = "refmlm",
    nbits: int = 8,
    separable: bool | None = None,
    fused: bool | None = None,
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
    container: str | None = None,
):
    """Run one bank filter over an image batch through the selected multiplier.

    The answer comes in the pixels' `container` (DESIGN.md §5): 'uint8'
    or 'uint16', by default the input's own (`container_of`: uint16
    images are answered in uint16, anything else in uint8). The output is
    clipped to the container's top and the separable column pass is sized
    from it; a method without a multiplier that wide raises ValueError.
    Only exec='local' serves a 'uint16' container.

    The execution plan -- dataflow and grid organization -- resolves through the per-backend plan cache
    (DESIGN.md §11): on default arguments a tuned `PlanConfig` for this
    (filter, batch/image shape) wins, and a cache miss reproduces the
    fixed pre-plan defaults. Explicit arguments always override.
    `separable=False` forces the direct KxK window (bit-identical for
    exact multipliers -- asserted in tests); `separable=True` admits only
    the two 1-D pass dataflows. Of those, fused=True runs both passes in
    one kernel (DESIGN.md §7) and fused=False forces the two-kernel
    dataflow with its HBM intermediate (the before/after benchmark axis).
    interpret=None autodetects the backend. The
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
    container = _container(container, imgs)
    if container != "uint8" and exec != "local":
        raise ValueError(f"exec={exec!r} answers uint8 pixels only; "
                         f"{container} pixels take exec='local'")
    filter_kw = dict(method=method, nbits=nbits, separable=separable,
                     fused=fused, block_rows=block_rows,
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
    widths = filter_nbits(spec, nbits, container)
    check_method_nbits(method, widths)
    arr, orig = _normalize(imgs)
    n, h, w = arr.shape
    kh, kw = spec.ksize
    plan = resolve_plan(spec.name, n, h, w, kh, kw,
                        separable_ok=spec.separable,
                        separable=separable, fused=fused,
                        block_rows=block_rows, block_cols=block_cols,
                        batch_fold=batch_fold)
    out = _apply(arr, spec, method, nbits, plan.dataflow != "direct",
                 plan.dataflow == "fused", plan.block_rows,
                 plan.block_cols, plan.batch_fold, interpret, container)
    return _restore(out, orig)


def resolve_filter_blocks(
    filt: FilterSpec | str,
    n: int,
    h: int,
    w: int,
    *,
    method: str = "refmlm",
    separable: bool | None = None,
    fused: bool | None = None,
    interpret: bool | None = None,
) -> "BlockConfig":
    """The grid organization `apply_filter` would resolve for an (n, h, w)
    batch of `filt` -- dataflow kind and tap extents included, one
    `repro.tuning.resolve_blocks` consult total.

    This is the serving layer's per-bucket memoisation hook (DESIGN.md
    §10): resolve once per (bucket, coalesced batch size), then pin the
    fields explicitly on every `apply_filter` dispatch so the steady-state
    hot path does no cache re-resolution (explicit values win and
    short-circuit the lookup). Outputs are bit-identical across grid
    organizations (§8), so pinning is throughput-only. Note `block_cols`
    is returned in the cache's vocabulary: None means full width, which
    pins explicitly as `block_cols=w`.
    """
    from repro.tuning import resolve_blocks_cached

    interpret = resolve_interpret(interpret)
    spec = get_filter(filt) if isinstance(filt, str) else filt
    separable = spec.separable if separable is None else separable
    fused = separable if fused is None else fused
    if fused and separable:
        kind = "fused"
        kh, kw = len(spec.sep_col), len(spec.sep_row)
    else:
        kind = "direct"
        kh, kw = np.shape(spec.taps)
    return resolve_blocks_cached(kind, n, h, w, kh, kw, interpret)


def resolve_filter_plan(
    filt: FilterSpec | str,
    n: int,
    h: int,
    w: int,
    *,
    method: str = "refmlm",
    separable: bool | None = None,
    fused: bool | None = None,
    interpret: bool | None = None,
) -> PlanConfig:
    """The fully-concrete execution plan `apply_filter` would run for an
    (n, h, w) batch of `filt`: dataflow and grid organization, one
    plan-cache consult total (DESIGN.md §11).

    This is the serving layer's per-bucket memoisation hook (DESIGN.md
    §10): resolve once per (bucket, coalesced batch size), then pin every
    field explicitly on each `apply_filter` dispatch so the steady-state
    hot path takes `resolve_plan`'s fully-explicit fast path and does no
    cache re-resolution. Fields the plan defers (an untuned shape) are
    concretized here through the §8 block cache of the matching pass kind
    (a full-width tile pins explicitly as `block_cols=w`). Outputs are
    bit-identical across plans, so pinning is throughput-only.
    """
    from repro.tuning import resolve_blocks_cached

    interpret = resolve_interpret(interpret)
    spec = get_filter(filt) if isinstance(filt, str) else filt
    plan = resolve_plan(spec.name, n, h, w, *spec.ksize,
                        separable_ok=spec.separable,
                        separable=separable, fused=fused)
    if plan.dataflow == "fused":
        kind = "fused"
        kh, kw = len(spec.sep_col), len(spec.sep_row)
    elif plan.dataflow == "two_pass":
        # the second (column) pass carries the row halo; its §8 entry sizes
        # the pinned grid when the plan defers
        kind = "direct"
        kh, kw = len(spec.sep_col), 1
    else:
        kind = "direct"
        kh, kw = spec.ksize
    if None in (plan.block_rows, plan.block_cols, plan.batch_fold):
        base = resolve_blocks_cached(kind, n, h, w, kh, kw, interpret)
        plan = PlanConfig(
            plan.dataflow,
            base.block_rows if plan.block_rows is None else plan.block_rows,
            (plan.block_cols if plan.block_cols is not None
             else w if base.block_cols is None else base.block_cols),
            base.batch_fold if plan.batch_fold is None else plan.batch_fold)
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

    The batch crosses to the device in `batch_dtype(imgs)`: the pixels'
    own container (1 or 2 bytes a pixel) when they share one, widened to
    int32 inside the jitted conv passes; `BATCH_DTYPE` otherwise. Their
    container (`container_of` the first image) is handed to
    `apply_filter` explicitly, and the outputs come back in it.

    The steps are the serve worker's phase spans (`repro.obs.trace`,
    recorded only while a recorder is active): 'prep' (copy the images
    into one zero-padded batch), 'upload' (`jax.device_put` to the default
    device, which the executor sets to its pool member's), 'launch' (the
    `apply_filter` dispatch), 'device_wait' (`block_until_ready`) and
    'fetch' (the copy back and the split). The scale-out modes place
    their own inputs, so they have no 'upload' or 'device_wait'. Only
    while tracing does the upload block until the batch is on the
    device, which splits the copy from the device run; otherwise it
    stays asynchronous.
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
    container = container_of(imgs[0])
    with obs_trace.span("prep"):
        batch = np.empty((max(n, pad_to or 0), *shape), batch_dtype(imgs))
        for i, im in enumerate(imgs):
            batch[i] = im
        batch[n:] = 0
    local = kw.get("exec", "local") == "local"
    if local:
        with obs_trace.span("upload"):
            batch = jax.device_put(batch)
            if obs_trace.tracing():
                batch.block_until_ready()
    with obs_trace.span("launch"):
        out = apply_filter(batch, filt, container=container, **kw)
    if local:
        with obs_trace.span("device_wait"):
            out.block_until_ready()
    with obs_trace.span("fetch"):
        out = np.asarray(out)
        return [out[i] for i in range(n)]


def filter_bank_apply(
    imgs: Array,
    filters: tuple[str, ...] | None = None,
    *,
    method: str = "refmlm",
    **kw,
) -> dict[str, Array]:
    """Run many filters over one batch: name -> output batch, in the
    pixels' container (`apply_filter`)."""
    names = FILTER_NAMES if filters is None else tuple(filters)
    return {name: apply_filter(imgs, name, method=method, **kw)
            for name in names}


__all__ = ["BATCH_DTYPE", "CONTAINERS", "EXEC_MODES", "apply_filter",
           "apply_filter_batch", "batch_dtype", "check_method_nbits",
           "container_of", "container_top", "filter_bank_apply",
           "filter_nbits", "resolve_filter_blocks", "resolve_filter_plan"]
