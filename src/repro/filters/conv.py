"""Batched multiplier-selectable 2-D convolution Pallas kernels (DESIGN.md §5,
performance engineering in §7, grid organization in §8).

Generalization of the original single-image 3x3 Gaussian kernel: one kernel
body serves every filter of the bank, in three dataflows --

  * direct    -- one pass over the (kh, kw) tap table;
  * separable -- a horizontal (1, kw) pass producing a raw int32 accumulator
                 image, then a vertical (kh, 1) pass that normalizes. Two
                 1-D passes cost kh+kw tap products per pixel vs kh*kw, the
                 VMEM analogue of FPGA line-buffer reuse (arXiv:1710.05154);
  * fused separable -- both 1-D passes in ONE `pallas_call`: the horizontal
                 pass lands in a VMEM band carrying a kh//2-row halo and the
                 vertical pass consumes it in-kernel, eliminating the HBM
                 round-trip of the (N, H, W) int32 intermediate
                 (`fused_separable_pass`, DESIGN.md §7).

Throughput-first grid (DESIGN.md §8): every pass runs on a
`grid = (N, H/block_rows, W/block_cols)` of independent output tiles, all
three axes declared `parallel` on compiled backends
(`core.platform.grid_compiler_params`):

  * row bands -- the kh vertical taps are kh row-shifted views of the
    zero-padded input (the FIFO line buffers), each blocked into bands;
  * column tiles -- when `block_cols` is narrower than the image, each view
    is fed twice at column-block indices j and j+1; their concatenation
    carries the kw//2-column halo (the same paired-view trick the fused
    kernel uses for its row halo);
  * batch fold -- small-image batches are folded into the row axis: each
    image gets its own kh//2-row zero halo and the stack becomes one tall
    (1, N*(H+2*ph), W) image, so the whole batch rides the parallel row-tile
    axis instead of a serial leading batch axis (bit-identical: the embedded
    zero halos reproduce each image's own zero padding, and the halo output
    rows are cropped on unfold).

Block shapes default to the per-backend autotune cache
(`repro.tuning.resolve_blocks`; explicit arguments always override), and
row/column padding to tile multiples happens here -- callers pass any
(N, H, W).

The (kh, kw) coefficient table rides in SMEM and is read as scalars, like
the FPGA's coefficient registers; every tap product routes through the
selected multiplier via the signed-magnitude contract (DESIGN.md §4):
p = sgn(t)*sgn(c)*mult(|t|,|c|), so negative coefficients (sharpen, Sobel,
Laplacian) reuse the unsigned paper multipliers unchanged. The in-register
accumulation is the CSA tree, carried at the narrowest width the exact
table-bound analysis admits (int16 when every |partial sum| < 2**15, the
direct-path analogue of `second_pass_nbits`; DESIGN.md §8); `post` then
applies the filter's fixed-point normalization ('clip'), gradient-magnitude
display ('abs'), or nothing ('none', the separable intermediate) in int32.

Tap-product implementations (`mult_impl`, DESIGN.md §7):
  * 'recurse' -- expand the selected multiplier's dataflow per tap (the
    digit-plane-flattened KOM recursion for 'refmlm');
  * 'kcm'     -- constant-coefficient fast path: coefficients are trace-time
    constants, so each tap is a `repro.core.kcm` product-table gather
    (sign baked in), bit-identical to 'recurse' for every method;
  * 'auto'    -- 'kcm' whenever the taps are static (not traced) and the
    pass runs under the interpreter, else 'recurse'. Compiled TPU passes
    always take 'recurse': Mosaic lowers only 2-D gathers, not the 1-D
    ROM lookup.

Multiplier methods: 'exact', 'refmlm', 'refmlm_nc', 'mitchell',
'mitchell_ecc{k}', 'odma' -- see repro/core and DESIGN.md §1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kcm import METHODS, filter_tables, tables_acc_bound, tap_multiplier
from repro.core.platform import grid_compiler_params, resolve_interpret
from repro.tuning import choose_block_rows, resolve_blocks
from repro.tuning.blocks import min_block_cols, min_block_rows, round_up

MULT_IMPLS = ("recurse", "kcm", "auto")

_ACC_DTYPES = {"int16": jnp.int16, "int32": jnp.int32}


def accumulate_taps(bands, k_ref, acc_shape, *, kh: int, kw: int, w: int,
                    method: str, nbits: int, tables=None,
                    acc_dtype=jnp.int32) -> Array:
    """Shared CSA-tree body: Σ_taps sgn * mult(|tap|, |coeff|) over a band.

    `bands` -- kh arrays of shape (..., w + kw - 1); `k_ref` -- the (kh, kw)
    SMEM coefficient table. One definition serves every dataflow so the
    direct / separable / fused paths are bit-exact by construction.

    With `tables` (a (kh*kw, 2**nbits) KCM ROM stack, coefficient signs
    baked in) each tap product becomes a gather -- `k_ref`/`method` are then
    unused and the contract reduces to sgn(tap) * tables[tap_idx][|tap|].
    `acc_dtype` is the accumulator carry width; callers may narrow it to
    int16 only when the exact bound analysis proves every partial sum fits
    (`tables_acc_bound`, DESIGN.md §8) -- the sum is then value-identical to
    the int32 carry.
    """
    acc = jnp.zeros(acc_shape, acc_dtype)
    mult = None if tables is not None else tap_multiplier(method)
    for di in range(kh):
        band = bands[di]
        for dj in range(kw):
            tap = band[..., dj : dj + w]
            if tables is not None:
                prod = jnp.take(tables[di * kw + dj], jnp.abs(tap), axis=0)
                term = jnp.sign(tap).astype(acc_dtype) * prod.astype(acc_dtype)
            else:
                c = k_ref[di, dj]
                prod = mult(jnp.abs(tap),
                            jnp.broadcast_to(jnp.abs(c), tap.shape), nbits)
                term = (jnp.sign(c) * jnp.sign(tap) * prod).astype(acc_dtype)
            acc = acc + term
    return acc


def apply_post(acc: Array, *, post: str, shift: int) -> Array:
    """Fixed-point epilogue: rounding shift + clip / abs / raw (DESIGN.md §5).

    Always widens to int32 first so a narrow accumulator keeps rounding
    headroom (the carry bound covers the sum, not the +2**(shift-1) bias).
    """
    acc = acc.astype(jnp.int32)
    if post == "none":
        return acc
    if post == "abs":
        acc = jnp.abs(acc)
    rounded = (acc + (1 << (shift - 1))) >> shift if shift > 0 else acc
    if post in ("clip", "abs"):
        return jnp.clip(rounded, 0, 255)
    raise ValueError(f"unknown post {post!r}")


@functools.lru_cache(maxsize=None)
def _host_tables(method: str, taps_key: tuple, shape: tuple, nbits: int):
    """Stacked KCM ROMs (narrow dtype) + their exact accumulator bound."""
    taps = np.asarray(taps_key, np.int64).reshape(shape)
    stack = filter_tables(method, taps, nbits)
    return stack, tables_acc_bound(stack)


@functools.lru_cache(maxsize=None)
def _device_tables(method: str, taps_key: tuple, shape: tuple, nbits: int):
    """Device-resident ROM stack, cached per coefficient table.

    `product_table` already caches the per-coefficient host ROMs; this layer
    keeps the stacked, device-put array out of the per-call hot path (the
    16-bit second-pass stack is ~128 KiB per tap at the narrowed width).
    Forced eager: the cached array must be a concrete constant even when
    the first request arrives inside a trace (shard_map in the distributed
    path, DESIGN.md §9) -- an lru-cached tracer would leak into every
    later call."""
    with jax.ensure_compile_time_eval():
        return jnp.asarray(_host_tables(method, taps_key, shape, nbits)[0])


def _tables_for(method: str, taps, nbits: int):
    """-> (device ROM stack, accumulator carry dtype name)."""
    flat = np.asarray(taps, np.int64)
    key = (method, tuple(flat.reshape(-1).tolist()), flat.shape, nbits)
    bound = _host_tables(*key)[1]
    if bound >= (1 << 31):
        raise ValueError(f"accumulator bound {bound} exceeds the int32 "
                         "datapath; narrow the taps or nbits")
    acc = "int16" if bound < (1 << 15) else "int32"
    return _device_tables(*key), acc


def _is_static(taps) -> bool:
    """True iff `taps` has concrete (trace-time-constant) values."""
    try:
        np.asarray(taps)
        return True
    except Exception:                                    # jax Tracer
        return False


def _resolve_mult_impl(mult_impl: str, *tap_arrays, interpret: bool) -> str:
    """'auto' -> 'kcm' for static taps under the interpreter, else
    'recurse'. Compiled (Mosaic) passes never take 'kcm': its ROM lookup is
    a 1-D gather, which Mosaic does not lower."""
    if mult_impl not in MULT_IMPLS:
        raise ValueError(f"mult_impl must be one of {MULT_IMPLS}, got {mult_impl!r}")
    static = all(_is_static(t) for t in tap_arrays)
    if mult_impl == "auto":
        return "kcm" if static and interpret else "recurse"
    if mult_impl == "kcm" and not interpret:
        raise ValueError("mult_impl='kcm' runs only under the Pallas "
                         "interpreter: Mosaic cannot lower the KCM table "
                         "gather; use 'recurse' (bit-identical) or 'auto'")
    if mult_impl == "kcm" and not static:
        raise ValueError("mult_impl='kcm' needs trace-time-constant taps; "
                         "traced coefficients must use 'recurse'")
    return mult_impl


# ----------------------------------------------------------------- batch fold

def _fold_batch(imgs: Array, ph: int) -> Array:
    """(N, H, W) -> (1, N*(H+2*ph), W): stack the images into one tall image,
    each carrying its own ph-row zero halo, so the batch rides the parallel
    row-tile grid axis (DESIGN.md §8). The embedded halos reproduce exactly
    the zero rows per-image padding would read, so every kept output row is
    bit-identical to the unfolded pass."""
    n, h, w = imgs.shape
    if ph:
        imgs = jnp.pad(imgs, ((0, 0), (ph, ph), (0, 0)))
    return imgs.reshape(1, n * (h + 2 * ph), w)


def _unfold_batch(out: Array, n: int, h: int, ph: int) -> Array:
    """Inverse of `_fold_batch` on the conv output: re-split the tall image
    and drop each image's halo output rows (computed from zeros, unused)."""
    return out.reshape(n, h + 2 * ph, out.shape[-1])[:, ph : ph + h]


# ---------------------------------------------------------------- single pass

def _kernel(coef_ref, *refs, kh: int, kw: int, method: str, nbits: int,
            shift: int, post: str, kcm: bool, tiled: bool, acc: str):
    *band_refs, o_ref = refs
    bc = o_ref.shape[-1]
    if tiled:
        # paired column-block views j / j+1: their concatenation holds the
        # bc + kw - 1 input columns feeding this tile (DESIGN.md §8)
        bands = [jnp.concatenate((band_refs[2 * di][0], band_refs[2 * di + 1][0]),
                                 axis=-1)[:, : bc + kw - 1] for di in range(kh)]
    else:
        bands = [band_refs[di][0] for di in range(kh)]  # each (br, bc + kw - 1)
    tacc = accumulate_taps(bands, None if kcm else coef_ref, o_ref.shape[1:],
                           kh=kh, kw=kw, w=bc, method=method, nbits=nbits,
                           tables=coef_ref[...] if kcm else None,
                           acc_dtype=_ACC_DTYPES[acc])
    o_ref[...] = apply_post(tacc, post=post, shift=shift)[None]


def _pass_call(imgs: Array, coef: Array, coef_spec, kernel, *, kh: int,
               kw: int, block_rows: int, bc: int, tiled: bool,
               interpret: bool) -> Array:
    """Shared pallas_call plumbing for one tiled convolution pass.

    `bc`/`tiled` come pre-derived from `_dispatch` (the single source): the
    kernel's static band-unpacking mode must match the spec layout built
    here, so both must be decided in one place.
    """
    n, h, w = imgs.shape
    br = block_rows
    ph, pw = kh // 2, kw // 2
    h2, w2 = round_up(h, br), round_up(w, bc)
    # Rows: ph halo above and below the (padded-to-band) output domain.
    # Cols: pw halo; when tiled, right-pad to (W/bc + 1) column blocks so the
    # paired view j+1 exists for the last tile (zeros, read only as halo).
    right = pw + (w2 - w) + (bc - 2 * pw if tiled else 0)
    padded = jnp.pad(imgs.astype(jnp.int32),
                     ((0, 0), (ph, ph + h2 - h), (pw, right)))
    views = [padded[:, di : di + h2, :] for di in range(kh)]  # line buffers
    if tiled:
        specs = []
        for _ in range(kh):
            specs.append(pl.BlockSpec((1, br, bc), lambda nn, i, j: (nn, i, j)))
            specs.append(pl.BlockSpec((1, br, bc), lambda nn, i, j: (nn, i, j + 1)))
        views = [v for v in views for _ in (0, 1)]
    else:
        specs = [pl.BlockSpec((1, br, w2 + 2 * pw), lambda nn, i, j: (nn, i, 0))
                 for _ in range(kh)]
    grid = (n, h2 // br, w2 // bc)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, h2, w2), jnp.int32),
        grid=grid,
        in_specs=[coef_spec, *specs],
        out_specs=pl.BlockSpec((1, br, bc), lambda nn, i, j: (nn, i, j)),
        compiler_params=grid_compiler_params(
            ("parallel", "parallel", "parallel"), interpret),
        interpret=interpret,
    )(coef, *views)
    return out[:, :h, :w]


@functools.partial(jax.jit, static_argnames=(
    "method", "nbits", "shift", "post", "block_rows", "block_cols",
    "batch_fold", "interpret"))
def _conv2d_recurse(imgs, taps, *, method, nbits, shift, post, block_rows,
                    block_cols, batch_fold, interpret):
    kh, kw = taps.shape
    spec = pl.BlockSpec((kh, kw), lambda nn, i, j: (0, 0),
                        memory_space=pltpu.SMEM)

    def call(x, bc, tiled):
        k = functools.partial(_kernel, kh=kh, kw=kw, method=method,
                              nbits=nbits, shift=shift, post=post, kcm=False,
                              tiled=tiled, acc="int32")
        return _pass_call(x, taps, spec, k, kh=kh, kw=kw,
                          block_rows=block_rows, bc=bc, tiled=tiled,
                          interpret=interpret)

    return _dispatch(imgs, call, kh=kh, kw=kw, batch_fold=batch_fold,
                     block_cols=block_cols)


@functools.partial(jax.jit, static_argnames=(
    "kh", "kw", "shift", "post", "block_rows", "block_cols", "batch_fold",
    "interpret", "acc"))
def _conv2d_kcm(imgs, tables, *, kh, kw, shift, post, block_rows, block_cols,
                batch_fold, interpret, acc):
    spec = pl.BlockSpec(tables.shape, lambda nn, i, j: (0, 0))  # whole ROM, VMEM

    def call(x, bc, tiled):
        k = functools.partial(_kernel, kh=kh, kw=kw, method="", nbits=0,
                              shift=shift, post=post, kcm=True, tiled=tiled,
                              acc=acc)
        return _pass_call(x, tables, spec, k, kh=kh, kw=kw,
                          block_rows=block_rows, bc=bc, tiled=tiled,
                          interpret=interpret)

    return _dispatch(imgs, call, kh=kh, kw=kw, batch_fold=batch_fold,
                     block_cols=block_cols)


def _dispatch(imgs: Array, call, *, kh: int, kw: int, batch_fold: bool,
              block_cols: int | None) -> Array:
    """Single source of the column-tile decision + the fold-into-rows
    transform around one pass (DESIGN.md §8). `call(x, bc, tiled)` receives
    the resolved tile width and tiling flag so the kernel's static
    band-unpacking mode and the pass's spec layout can never disagree."""
    n, h, w = imgs.shape
    ph, pw = kh // 2, kw // 2
    bc = w if block_cols is None else min(int(block_cols), w)
    tiled = bc < w
    if tiled and bc < min_block_cols(kw):
        raise ValueError(f"block_cols={bc} too narrow for a {pw}-column halo")
    if batch_fold and n > 1:
        out = call(_fold_batch(imgs.astype(jnp.int32), ph), bc, tiled)
        return _unfold_batch(out, n, h, ph)
    return call(imgs.astype(jnp.int32), bc, tiled)


def conv2d_pass(
    imgs: Array,
    taps: Array,
    *,
    method: str = "refmlm",
    nbits: int = 8,
    shift: int = 8,
    post: str = "clip",
    block_rows: int | None = None,
    block_cols: int | None = None,
    batch_fold: bool | None = None,
    interpret: bool | None = None,
    mult_impl: str = "auto",
) -> Array:
    """One batched convolution pass: (N, H, W) int32 -> (N, H, W) int32.

    Any (N, H, W) is accepted: the pass pads rows/columns to tile multiples
    internally and crops the output back. Unset grid fields (`block_rows`,
    `block_cols`, `batch_fold`) resolve through the per-backend autotune
    cache, then the heuristic (`repro.tuning.resolve_blocks`, DESIGN.md §8);
    explicit values always win. Input may be signed (the separable
    intermediate); `nbits` must cover the widest |operand| on either side of
    each tap product. interpret=None autodetects the backend (DESIGN.md §7);
    mult_impl picks the tap-product implementation (module docstring).
    """
    interpret = resolve_interpret(interpret)
    impl = _resolve_mult_impl(mult_impl, taps, interpret=interpret)
    n, h, w = imgs.shape
    kh, kw = np.shape(taps)     # list/tuple taps accepted, Tracers untouched
    cfg = resolve_blocks("direct", n, h, w, kh, kw, impl,
                         block_rows=block_rows, block_cols=block_cols,
                         batch_fold=batch_fold, interpret=interpret)
    if impl == "kcm":
        taps_np = np.asarray(taps)
        tables, acc = _tables_for(method, taps_np, nbits)
        return _conv2d_kcm(imgs, tables, kh=kh, kw=kw, shift=shift, post=post,
                           block_rows=cfg.block_rows,
                           block_cols=cfg.block_cols,
                           batch_fold=cfg.batch_fold, interpret=interpret,
                           acc=acc)
    return _conv2d_recurse(imgs, jnp.asarray(taps, jnp.int32), method=method,
                           nbits=nbits, shift=shift, post=post,
                           block_rows=cfg.block_rows,
                           block_cols=cfg.block_cols,
                           batch_fold=cfg.batch_fold, interpret=interpret)


# ------------------------------------------------------------ fused separable

def _fused_kernel(row_ref, col_ref, *refs, kh: int, kw: int, method: str,
                  nbits: int, nbits2: int, shift: int, post: str, kcm: bool,
                  tiled: bool):
    """Both separable passes on one tile (DESIGN.md §7/§8 halo math).

    The band refs are block views of the same padded image whose
    concatenation holds the (br + 2*ph, bc + 2*pw) input window feeding this
    tile's horizontal pass: row views i and i+1, and -- when column-tiled --
    the 2x2 of (i, j), (i, j+1), (i+1, j), (i+1, j+1). The horizontal
    accumulator never leaves VMEM.
    """
    *band_refs, o_ref = refs
    rows, bc = o_ref.shape[1], o_ref.shape[2]
    ph, pw = kh // 2, kw // 2
    if tiled:
        tl, tr, bl, brr = (r[0] for r in band_refs)
        full = jnp.concatenate(
            (jnp.concatenate((tl, tr), axis=-1),
             jnp.concatenate((bl, brr), axis=-1)),
            axis=0)[: rows + 2 * ph, : bc + 2 * pw]
    else:
        full = jnp.concatenate((band_refs[0][0], band_refs[1][0]),
                               axis=0)[: rows + 2 * ph]
    hacc = accumulate_taps([full], None if kcm else row_ref,
                           (rows + 2 * ph, bc), kh=1, kw=kw, w=bc,
                           method=method, nbits=nbits,
                           tables=row_ref[...] if kcm else None)
    vbands = [hacc[di : di + rows] for di in range(kh)]
    acc = accumulate_taps(vbands, None if kcm else col_ref, (rows, bc),
                          kh=kh, kw=1, w=bc, method=method, nbits=nbits2,
                          tables=col_ref[...] if kcm else None)
    o_ref[...] = apply_post(acc, post=post, shift=shift)[None]


def _fused_call(imgs: Array, row, col, row_spec, col_spec, kernel, *,
                kh: int, kw: int, block_rows: int, bc: int, tiled: bool,
                interpret: bool) -> Array:
    n, h, w = imgs.shape
    br = block_rows
    ph, pw = kh // 2, kw // 2
    assert br >= 2 * ph, f"block_rows={br} too shallow for a {ph}-row halo"
    h2, w2 = round_up(h, br), round_up(w, bc)
    nb, ncb = h2 // br, w2 // bc
    # ph halo rows on top; bottom-pad so row view i+1 exists for every band
    # (the extra rows are zeros and only ever read as halo). Columns follow
    # the same scheme when tiled: right-pad to ncb+1 blocks for view j+1.
    right = pw + (w2 - w) + (bc - 2 * pw if tiled else 0)
    padded = jnp.pad(imgs.astype(jnp.int32),
                     ((0, 0), (ph, (nb + 1) * br - h - ph), (pw, right)))
    if tiled:
        band = (1, br, bc)
        view_specs = [
            pl.BlockSpec(band, lambda nn, i, j: (nn, i, j)),
            pl.BlockSpec(band, lambda nn, i, j: (nn, i, j + 1)),
            pl.BlockSpec(band, lambda nn, i, j: (nn, i + 1, j)),
            pl.BlockSpec(band, lambda nn, i, j: (nn, i + 1, j + 1)),
        ]
    else:
        band = (1, br, w2 + 2 * pw)
        view_specs = [
            pl.BlockSpec(band, lambda nn, i, j: (nn, i, 0)),
            pl.BlockSpec(band, lambda nn, i, j: (nn, i + 1, 0)),
        ]
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, h2, w2), jnp.int32),
        grid=(n, nb, ncb),
        in_specs=[row_spec, col_spec, *view_specs],
        out_specs=pl.BlockSpec((1, br, bc), lambda nn, i, j: (nn, i, j)),
        compiler_params=grid_compiler_params(
            ("parallel", "parallel", "parallel"), interpret),
        interpret=interpret,
    )(row, col, *[padded] * len(view_specs))
    return out[:, :h, :w]


@functools.partial(jax.jit, static_argnames=(
    "method", "nbits", "nbits2", "shift", "post", "block_rows", "block_cols",
    "batch_fold", "interpret"))
def _fused_sep_recurse(imgs, row, col, *, method, nbits, nbits2, shift, post,
                       block_rows, block_cols, batch_fold, interpret):
    kh, kw = col.shape[0], row.shape[1]
    smem = functools.partial(pl.BlockSpec, index_map=lambda nn, i, j: (0, 0),
                             memory_space=pltpu.SMEM)

    def call(x, bc, tiled):
        kernel = functools.partial(_fused_kernel, kh=kh, kw=kw, method=method,
                                   nbits=nbits, nbits2=nbits2, shift=shift,
                                   post=post, kcm=False, tiled=tiled)
        return _fused_call(x, row, col, smem((1, kw)), smem((kh, 1)), kernel,
                           kh=kh, kw=kw, block_rows=block_rows, bc=bc,
                           tiled=tiled, interpret=interpret)

    return _dispatch(imgs, call, kh=kh, kw=kw, batch_fold=batch_fold,
                     block_cols=block_cols)


@functools.partial(jax.jit, static_argnames=(
    "kh", "kw", "shift", "post", "block_rows", "block_cols", "batch_fold",
    "interpret"))
def _fused_sep_kcm(imgs, row_tables, col_tables, *, kh, kw, shift, post,
                   block_rows, block_cols, batch_fold, interpret):
    rspec = pl.BlockSpec(row_tables.shape, lambda nn, i, j: (0, 0))
    cspec = pl.BlockSpec(col_tables.shape, lambda nn, i, j: (0, 0))

    def call(x, bc, tiled):
        kernel = functools.partial(_fused_kernel, kh=kh, kw=kw, method="",
                                   nbits=0, nbits2=0, shift=shift, post=post,
                                   kcm=True, tiled=tiled)
        return _fused_call(x, row_tables, col_tables, rspec, cspec, kernel,
                           kh=kh, kw=kw, block_rows=block_rows, bc=bc,
                           tiled=tiled, interpret=interpret)

    return _dispatch(imgs, call, kh=kh, kw=kw, batch_fold=batch_fold,
                     block_cols=block_cols)


def fused_separable_pass(
    imgs: Array,
    row: Array,
    col: Array,
    *,
    method: str = "refmlm",
    nbits: int = 8,
    nbits2: int = 16,
    shift: int = 8,
    post: str = "clip",
    block_rows: int | None = None,
    block_cols: int | None = None,
    batch_fold: bool | None = None,
    interpret: bool | None = None,
    mult_impl: str = "auto",
) -> Array:
    """Fused separable convolution: both 1-D passes in one `pallas_call`.

    Bit-identical to `conv2d_pass(row, post='none')` followed by
    `conv2d_pass(col)` -- the horizontal accumulator band (with its
    kh//2-row halo) just stays in VMEM instead of round-tripping through
    HBM (DESIGN.md §7). `row` is the (kw,) horizontal filter at width
    `nbits`, `col` the (kh,) vertical filter at width `nbits2`
    (see `second_pass_nbits`). Grid fields default through the autotune
    cache exactly like `conv2d_pass` (DESIGN.md §8).
    """
    interpret = resolve_interpret(interpret)
    impl = _resolve_mult_impl(mult_impl, row, col, interpret=interpret)
    n, h, w = imgs.shape
    kh = int(np.asarray(col).size) if _is_static(col) else col.shape[-1]
    kw = int(np.asarray(row).size) if _is_static(row) else row.shape[-1]
    cfg = resolve_blocks("fused", n, h, w, kh, kw, impl,
                         block_rows=block_rows, block_cols=block_cols,
                         batch_fold=batch_fold, interpret=interpret)
    if cfg.block_rows < 2 * (kh // 2):
        if block_rows is not None:      # explicit values win or fail loud
            raise ValueError(f"block_rows={block_rows} too shallow for a "
                             f"{kh // 2}-row halo")
        cfg = cfg._replace(block_rows=min_block_rows(kh))
    if impl == "kcm":
        rt = _tables_for(method, row, nbits)[0]
        ct = _tables_for(method, col, nbits2)[0]
        return _fused_sep_kcm(imgs, rt, ct, kh=ct.shape[0], kw=rt.shape[0],
                              shift=shift, post=post,
                              block_rows=cfg.block_rows,
                              block_cols=cfg.block_cols,
                              batch_fold=cfg.batch_fold, interpret=interpret)
    row = jnp.asarray(row, jnp.int32).reshape(1, -1)
    col = jnp.asarray(col, jnp.int32).reshape(-1, 1)
    return _fused_sep_recurse(imgs, row, col, method=method, nbits=nbits,
                              nbits2=nbits2, shift=shift, post=post,
                              block_rows=cfg.block_rows,
                              block_cols=cfg.block_cols,
                              batch_fold=cfg.batch_fold, interpret=interpret)


def second_pass_nbits(intermediate_max: int, coeff_max: int) -> int:
    """Multiplier width for the separable column pass: the narrowest
    supported width covering both the row-pass accumulator magnitude and the
    column coefficients (8 for narrow filters, 16 in general)."""
    need = max(int(intermediate_max), int(coeff_max))
    for nb in (2, 4, 8, 16):
        if need < (1 << nb):
            return nb
    raise ValueError(
        f"separable intermediate {need} exceeds the 16-bit REFMLM datapath")


__all__ = [
    "METHODS",
    "MULT_IMPLS",
    "accumulate_taps",
    "apply_post",
    "choose_block_rows",
    "conv2d_pass",
    "fused_separable_pass",
    "second_pass_nbits",
    "tap_multiplier",
]
