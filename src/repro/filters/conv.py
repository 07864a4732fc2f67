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

Concrete taps (the bank's, host NumPy) are baked into the compiled kernel
as Python ints, like an FPGA's synthesis-time constants: a zero tap adds
nothing, and REFMLM runs the kom4 recursion with a constant operand
(`repro.core.refmlm.refmlm_const`), so every 2x2 base product whose
coefficient digit is 0 is dropped at trace time (DESIGN.md §7). Traced taps
(a caller that jits over them) ride in SMEM instead and are read as
scalars at run time, like the FPGA's coefficient registers. Each REFMLM
kernel build records its base products per output pixel in the process-wide
`KERNEL_METRICS` gauge `conv_tap_leaves{kernel, coef="baked"|"smem", ...}`.
Either way every tap product routes through the selected multiplier via
the signed-magnitude contract (DESIGN.md §4):
p = sgn(t)*sgn(c)*mult(|t|,|c|), so negative coefficients (sharpen, Sobel,
Laplacian) reuse the unsigned paper multipliers unchanged. The in-register
accumulation is the CSA tree, carried in int32; `post` then applies the
filter's fixed-point normalization ('clip'), gradient-magnitude display
('abs'), or nothing ('none', the separable intermediate), clipping to
`top`, the top of the pixels' container (255 for uint8, 65535 for uint16;
DESIGN.md §5).

One tap-product implementation, the selected multiplier's recursion, in
two forms chosen by `_is_static` (DESIGN.md §7): taps readable at trace
time are baked (`const_tap_multiplier`), traced taps ride in SMEM.

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

from repro.core.platform import grid_compiler_params, resolve_interpret
from repro.core.refmlm import const_base_mults, flat_base_mults
from repro.core.taps import METHODS, const_tap_multiplier, tap_multiplier
from repro.obs.metrics import MetricsRegistry
from repro.tuning import choose_block_rows, resolve_blocks
from repro.tuning.blocks import min_block_cols, min_block_rows, round_up

#: Kernel-build telemetry. Process-wide, like the jit cache whose builds it
#: records: a kernel traced once serves every later caller.
KERNEL_METRICS = MetricsRegistry()

_REFMLM_METHODS = ("refmlm", "refmlm_nc")


def accumulate_taps(bands, k_ref, acc_shape, *, kh: int, kw: int, w: int,
                    method: str, nbits: int) -> Array:
    """Shared CSA-tree body: Σ_taps sgn * mult(|tap|, |coeff|) over a band.

    `bands` -- kh arrays of shape (..., w + kw - 1); `k_ref` -- the (kh, kw)
    SMEM coefficient table, or the taps as nested tuples of Python ints,
    baked into the kernel: a zero tap is then skipped (sgn(0) = 0) and each
    product takes `const_tap_multiplier`. One definition serves every
    dataflow so the direct / separable / fused paths are bit-exact by
    construction.
    """
    acc = jnp.zeros(acc_shape, jnp.int32)
    baked = isinstance(k_ref, tuple)
    mult = const_tap_multiplier(method) if baked else tap_multiplier(method)
    for di in range(kh):
        band = bands[di]
        for dj in range(kw):
            if baked and k_ref[di][dj] == 0:
                continue
            tap = band[..., dj : dj + w]
            if baked:
                c = k_ref[di][dj]
                term = jnp.sign(tap) * mult(jnp.abs(tap), abs(c), nbits)
                term = -term if c < 0 else term
            else:
                c = k_ref[di, dj]
                prod = mult(jnp.abs(tap),
                            jnp.broadcast_to(jnp.abs(c), tap.shape), nbits)
                term = jnp.sign(c) * jnp.sign(tap) * prod
            acc = acc + term.astype(jnp.int32)
    return acc


def apply_post(acc: Array, *, post: str, shift: int, top: int = 255) -> Array:
    """Fixed-point epilogue: rounding shift + clip / abs / raw (DESIGN.md §5).

    'clip' and 'abs' clip to [0, top], the top of the pixels' container.
    """
    if post == "none":
        return acc
    if post == "abs":
        acc = jnp.abs(acc)
    rounded = (acc + (1 << (shift - 1))) >> shift if shift > 0 else acc
    if post in ("clip", "abs"):
        return jnp.clip(rounded, 0, top)
    raise ValueError(f"unknown post {post!r}")


def _baked(taps, shape: tuple[int, int]) -> tuple[tuple[int, ...], ...]:
    """Concrete taps as the hashable (kh, kw) nested int tuples a kernel
    bakes in (a static jit argument)."""
    rows = np.asarray(taps, np.int64).reshape(shape)
    return tuple(tuple(int(v) for v in row) for row in rows)


def _record_tap_leaves(kernel: str, method: str, passes) -> None:
    """Set `conv_tap_leaves`: the 2x2 base products per output pixel of one
    REFMLM kernel build. `passes` -- (taps, nbits) per pass, the taps either
    baked (nested int tuples: only non-zero coefficient digits count) or a
    traced SMEM table (every tap pays the full recursion). Other methods
    have no base products and record nothing."""
    if method not in _REFMLM_METHODS:
        return
    baked = isinstance(passes[0][0], tuple)
    if baked:
        leaves = sum(const_base_mults(abs(c), nb)
                     for taps, nb in passes for row in taps for c in row)
        label = " ".join("/".join("_".join(map(str, row)) for row in taps)
                         for taps, _ in passes)
    else:
        leaves = sum(taps.size * flat_base_mults(nb) for taps, nb in passes)
        label = " ".join("x".join(map(str, taps.shape)) for taps, _ in passes)
    KERNEL_METRICS.gauge("conv_tap_leaves").set(
        leaves, kernel=kernel, coef="baked" if baked else "smem",
        method=method, nbits="/".join(str(nb) for _, nb in passes),
        taps=label)


def _is_static(taps) -> bool:
    """True iff `taps` has concrete (trace-time-constant) values."""
    try:
        np.asarray(taps)
        return True
    except Exception:                                    # jax Tracer
        return False


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

def _kernel(*refs, kh: int, kw: int, method: str, nbits: int, shift: int,
            post: str, top: int, taps, tiled: bool):
    """`taps` -- the baked (kh, kw) int tuples; None when the first ref is
    the SMEM coefficient table."""
    *band_refs, o_ref = refs
    if taps is None:
        taps, *band_refs = band_refs
    bc = o_ref.shape[-1]
    if tiled:
        # paired column-block views j / j+1: their concatenation holds the
        # bc + kw - 1 input columns feeding this tile (DESIGN.md §8)
        bands = [jnp.concatenate((band_refs[2 * di][0], band_refs[2 * di + 1][0]),
                                 axis=-1)[:, : bc + kw - 1] for di in range(kh)]
    else:
        bands = [band_refs[di][0] for di in range(kh)]  # each (br, bc + kw - 1)
    tacc = accumulate_taps(bands, taps, o_ref.shape[1:], kh=kh, kw=kw, w=bc,
                           method=method, nbits=nbits)
    o_ref[...] = apply_post(tacc, post=post, shift=shift, top=top)[None]


def _pass_call(imgs: Array, coefs: tuple, coef_specs: tuple, kernel, *,
               kh: int, kw: int, block_rows: int, bc: int, tiled: bool,
               interpret: bool) -> Array:
    """Shared pallas_call plumbing for one tiled convolution pass.

    `coefs` -- the SMEM coefficient table ahead of the image views, or ()
    when the kernel bakes its taps.

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
        name="refmlm_conv_pass",
        in_specs=[*coef_specs, *specs],
        out_specs=pl.BlockSpec((1, br, bc), lambda nn, i, j: (nn, i, j)),
        compiler_params=grid_compiler_params(
            ("parallel", "parallel", "parallel"), interpret),
        interpret=interpret,
    )(*coefs, *views)
    return out[:, :h, :w]


@functools.partial(jax.jit, static_argnames=(
    "taps", "method", "nbits", "shift", "post", "top", "block_rows",
    "block_cols", "batch_fold", "interpret"))
def _conv2d_recurse(imgs, coef, *, taps, method, nbits, shift, post, top,
                    block_rows, block_cols, batch_fold, interpret):
    """`taps` -- the (kh, kw) coefficients as int tuples, baked into the
    kernel; or None, and `coef`, the traced (kh, kw) table, rides in SMEM."""
    if taps is None:
        kh, kw = coef.shape
        coefs = (coef,)
        specs = (pl.BlockSpec((kh, kw), lambda nn, i, j: (0, 0),
                              memory_space=pltpu.SMEM),)
    else:
        kh, kw = len(taps), len(taps[0])
        coefs = specs = ()
    _record_tap_leaves("refmlm_conv_pass", method,
                       [(coef if taps is None else taps, nbits)])

    def call(x, bc, tiled):
        k = functools.partial(_kernel, kh=kh, kw=kw, method=method,
                              nbits=nbits, shift=shift, post=post, top=top,
                              taps=taps, tiled=tiled)
        return _pass_call(x, coefs, specs, k, kh=kh, kw=kw,
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
    top: int = 255,
    block_rows: int | None = None,
    block_cols: int | None = None,
    batch_fold: bool | None = None,
    interpret: bool | None = None,
) -> Array:
    """One batched convolution pass: (N, H, W) int32 -> (N, H, W) int32.
    uint8 and uint16 input is widened to int32 inside the jit.

    Any (N, H, W) is accepted: the pass pads rows/columns to tile multiples
    internally and crops the output back. Unset grid fields (`block_rows`,
    `block_cols`, `batch_fold`) resolve through the per-backend autotune
    cache, then the heuristic (`repro.tuning.resolve_blocks`, DESIGN.md §8);
    explicit values always win. Input may be signed (the separable
    intermediate); `nbits` must cover the widest |operand| on either side of
    each tap product. `top` is the clip of 'clip' and 'abs', the top of
    the pixels' container. interpret=None autodetects the backend
    (DESIGN.md §7). Taps readable at trace time are baked into the kernel,
    traced taps ride in SMEM (module docstring).
    """
    interpret = resolve_interpret(interpret)
    n, h, w = imgs.shape
    kh, kw = np.shape(taps)     # list/tuple taps accepted, Tracers untouched
    cfg = resolve_blocks("direct", n, h, w, kh, kw,
                         block_rows=block_rows, block_cols=block_cols,
                         batch_fold=batch_fold, interpret=interpret)
    baked = _is_static(taps)
    return _conv2d_recurse(imgs,
                           None if baked else jnp.asarray(taps, jnp.int32),
                           taps=_baked(taps, (kh, kw)) if baked else None,
                           method=method, nbits=nbits, shift=shift, post=post,
                           top=top, block_rows=cfg.block_rows,
                           block_cols=cfg.block_cols,
                           batch_fold=cfg.batch_fold, interpret=interpret)


# ------------------------------------------------------------ fused separable

def _fused_kernel(*refs, kh: int, kw: int, method: str, nbits: int,
                  nbits2: int, shift: int, post: str, top: int, taps,
                  tiled: bool):
    """Both separable passes on one tile (DESIGN.md §7/§8 halo math).

    `taps` -- the baked ((1, kw), (kh, 1)) int tuples of the row and column
    filters; None when the first two refs are their SMEM coefficient
    tables.

    The band refs are block views of the same padded image whose
    concatenation holds the (br + 2*ph, bc + 2*pw) input window feeding this
    tile's horizontal pass: row views i and i+1, and -- when column-tiled --
    the 2x2 of (i, j), (i, j+1), (i+1, j), (i+1, j+1). The horizontal
    accumulator never leaves VMEM.
    """
    *band_refs, o_ref = refs
    if taps is None:
        row_ref, col_ref, *band_refs = band_refs
        taps = (row_ref, col_ref)
    row_taps, col_taps = taps
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
    hacc = accumulate_taps([full], row_taps, (rows + 2 * ph, bc), kh=1, kw=kw,
                           w=bc, method=method, nbits=nbits)
    vbands = [hacc[di : di + rows] for di in range(kh)]
    acc = accumulate_taps(vbands, col_taps, (rows, bc), kh=kh, kw=1, w=bc,
                          method=method, nbits=nbits2)
    o_ref[...] = apply_post(acc, post=post, shift=shift, top=top)[None]


def _fused_call(imgs: Array, coefs: tuple, coef_specs: tuple, kernel, *,
                kh: int, kw: int, block_rows: int, bc: int, tiled: bool,
                interpret: bool) -> Array:
    """`coefs` -- the row and column coefficient operands, or () when the
    kernel bakes its taps (as in `_pass_call`)."""
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
        name="refmlm_conv_fused",
        in_specs=[*coef_specs, *view_specs],
        out_specs=pl.BlockSpec((1, br, bc), lambda nn, i, j: (nn, i, j)),
        compiler_params=grid_compiler_params(
            ("parallel", "parallel", "parallel"), interpret),
        interpret=interpret,
    )(*coefs, *[padded] * len(view_specs))
    return out[:, :h, :w]


@functools.partial(jax.jit, static_argnames=(
    "taps", "method", "nbits", "nbits2", "shift", "post", "top",
    "block_rows", "block_cols", "batch_fold", "interpret"))
def _fused_sep_recurse(imgs, row, col, *, taps, method, nbits, nbits2, shift,
                       post, top, block_rows, block_cols, batch_fold,
                       interpret):
    """`taps` -- the ((1, kw), (kh, 1)) row and column filters as int
    tuples, baked into the kernel; or None, and the traced `row` (1, kw) and
    `col` (kh, 1) tables ride in SMEM."""
    if taps is None:
        kh, kw = col.shape[0], row.shape[1]
        smem = functools.partial(pl.BlockSpec,
                                 index_map=lambda nn, i, j: (0, 0),
                                 memory_space=pltpu.SMEM)
        coefs, specs = (row, col), (smem((1, kw)), smem((kh, 1)))
        passes = [(row, nbits), (col, nbits2)]
    else:
        kh, kw = len(taps[1]), len(taps[0][0])
        coefs = specs = ()
        passes = [(taps[0], nbits), (taps[1], nbits2)]
    _record_tap_leaves("refmlm_conv_fused", method, passes)

    def call(x, bc, tiled):
        kernel = functools.partial(_fused_kernel, kh=kh, kw=kw, method=method,
                                   nbits=nbits, nbits2=nbits2, shift=shift,
                                   post=post, top=top, taps=taps,
                                   tiled=tiled)
        return _fused_call(x, coefs, specs, kernel, kh=kh, kw=kw,
                           block_rows=block_rows, bc=bc, tiled=tiled,
                           interpret=interpret)

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
    top: int = 255,
    block_rows: int | None = None,
    block_cols: int | None = None,
    batch_fold: bool | None = None,
    interpret: bool | None = None,
) -> Array:
    """Fused separable convolution: both 1-D passes in one `pallas_call`.

    Bit-identical to `conv2d_pass(row, post='none')` followed by
    `conv2d_pass(col)` -- the horizontal accumulator band (with its
    kh//2-row halo) just stays in VMEM instead of round-tripping through
    HBM (DESIGN.md §7). `row` is the (kw,) horizontal filter at width
    `nbits`, `col` the (kh,) vertical filter at width `nbits2`
    (see `second_pass_nbits`); `top` is the clip, as in `conv2d_pass`.
    Grid fields default through the autotune cache exactly like
    `conv2d_pass` (DESIGN.md §8).
    """
    interpret = resolve_interpret(interpret)
    n, h, w = imgs.shape
    kh = int(np.asarray(col).size) if _is_static(col) else col.shape[-1]
    kw = int(np.asarray(row).size) if _is_static(row) else row.shape[-1]
    cfg = resolve_blocks("fused", n, h, w, kh, kw,
                         block_rows=block_rows, block_cols=block_cols,
                         batch_fold=batch_fold, interpret=interpret)
    if cfg.block_rows < 2 * (kh // 2):
        if block_rows is not None:      # explicit values win or fail loud
            raise ValueError(f"block_rows={block_rows} too shallow for a "
                             f"{kh // 2}-row halo")
        cfg = cfg._replace(block_rows=min_block_rows(kh))
    if _is_static(row) and _is_static(col):
        taps = (_baked(row, (1, kw)), _baked(col, (kh, 1)))
        row = col = None
    else:
        taps = None
        row = jnp.asarray(row, jnp.int32).reshape(1, -1)
        col = jnp.asarray(col, jnp.int32).reshape(-1, 1)
    return _fused_sep_recurse(imgs, row, col, taps=taps, method=method,
                              nbits=nbits, nbits2=nbits2, shift=shift,
                              post=post, top=top,
                              block_rows=cfg.block_rows,
                              block_cols=cfg.block_cols,
                              batch_fold=cfg.batch_fold, interpret=interpret)


def second_pass_nbits(intermediate_max: int, coeff_max: int) -> int:
    """Multiplier width for the separable column pass: the narrowest
    supported width covering both the row-pass accumulator magnitude and the
    column coefficients (8 for narrow filters, 16 for 8-bit pixels, 32 for
    16-bit pixels)."""
    need = max(int(intermediate_max), int(coeff_max))
    for nb in (2, 4, 8, 16, 32):
        if need < (1 << nb):
            return nb
    raise ValueError(
        f"separable intermediate {need} exceeds the 32-bit REFMLM datapath")


__all__ = [
    "METHODS",
    "accumulate_taps",
    "apply_post",
    "choose_block_rows",
    "KERNEL_METRICS",
    "conv2d_pass",
    "fused_separable_pass",
    "second_pass_nbits",
    "tap_multiplier",
]
