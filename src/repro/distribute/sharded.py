"""Sharded execution of the filter datapath: `shard_map` over a
(batch, rows) device mesh with halo-correct row bands (DESIGN.md §9).

Every wrapper here is bit-identical to its single-device counterpart: the
conv passes are pure integer dataflows whose outputs are invariant to the
grid organization (DESIGN.md §8), so distribution only has to hand each
shard the same input window the local pass would read. Whole images ride
the `batch` mesh axis with no communication at all; row bands ride the
`rows` axis and source their kh//2 halo rows one of two ways:

  * halo='exchange' -- neighbor exchange inside `shard_map`: each shard
    `ppermute`s its top/bottom ph rows to the shard below/above and
    concatenates what it receives. Shards at the global edges receive
    `ppermute`'s zero fill -- exactly the zero padding the local pass
    reads there, which is what makes the mode bit-identical for free.
    Communication is 2*ph*W words per shard per call.
  * halo='embedded' -- the PR-3 batch-fold trick lifted to the mesh: the
    host pre-slices overlapping (hl + 2*ph)-row windows of the zero-padded
    global image and shards those, so no collective runs at all and the
    entire pass is embarrassingly parallel. Costs one extra host-side copy
    of the input plus 2*ph/hl redundant rows of transfer per shard.

Either way each shard runs the ordinary local pass on its extended band
and crops the ph halo output rows (computed from neighbor data, owned by
the neighbor). The pass inside `shard_map` traces with the *shard-local*
shape, so the block-shape tuning cache (`repro.tuning`, DESIGN.md §8) is
consulted with per-shard keys -- a winner tuned for the global image shape
is never silently inherited by a shard (`mesh.shard_local_shape` names the
key; asserted in tests/test_distribute.py).

Non-divisible batches pad with zero images, non-divisible (or
smaller-than-one-shard) row counts pad with zero rows; both pads reproduce
the zero halo the local path reads anyway and are cropped from the output
(`mesh.shard_dims`).
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array
from jax.sharding import Mesh, PartitionSpec as P

from repro.distribute.mesh import BATCH_AXIS, ROWS_AXIS, filter_mesh, shard_dims
from repro.filters.bank import FilterSpec, get_filter
from repro.obs import trace as obs_trace
from repro.runtime.fault import SITE_SHARD
from repro.runtime.fault import probe as fault_probe

HALO_MODES = ("exchange", "embedded")

#: (pass_key, mesh, ph, halo) -> jitted sharded callable (keeps the
#: shard_map retrace out of the per-call hot path; see `_sharded_fn`).
_FN_CACHE: dict[tuple, Callable] = {}


def _exchange_body(pass_fn: Callable, ph: int, nr: int) -> Callable:
    """shard_map body for halo='exchange': fetch ph neighbor rows, run the
    local pass on the extended band, crop the halo output rows."""

    def body(x: Array) -> Array:        # x: (nl, hl, w) shard-local
        if nr > 1 and ph > 0:
            up = jax.lax.ppermute(x[:, -ph:], ROWS_AXIS,
                                  [(i, i + 1) for i in range(nr - 1)])
            dn = jax.lax.ppermute(x[:, :ph], ROWS_AXIS,
                                  [(i + 1, i) for i in range(nr - 1)])
            # edge shards receive ppermute's zero fill == the local path's
            # zero padding, so no special-casing of the global borders
            ext = jnp.concatenate([up, x, dn], axis=1)
            return pass_fn(ext)[:, ph:-ph]
        return pass_fn(x)

    return body


def _embedded_body(pass_fn: Callable, ph: int, hl: int) -> Callable:
    """shard_map body for halo='embedded': the shard already holds its
    (hl + 2*ph)-row window; run the pass and keep the owned rows."""

    def body(xb: Array) -> Array:       # xb: (1, nl, hl + 2*ph, w)
        out = pass_fn(xb[0])
        return out[None, :, ph:ph + hl] if ph else out[None]

    return body


def _sharded_fn(pass_key: tuple, pass_fn: Callable, mesh: Mesh, ph: int,
                halo: str, hl: int) -> Callable:
    """Build (or fetch) the jitted shard_map'd executor for one config."""
    key = (pass_key, mesh, ph, halo, hl)
    fn = _FN_CACHE.get(key)
    if fn is None:
        spec = P(BATCH_AXIS, ROWS_AXIS)
        if halo == "exchange":
            nr = mesh.devices.shape[1]
            body = _exchange_body(pass_fn, ph, nr)
            sm = jax.shard_map(body, mesh=mesh, in_specs=spec,
                               out_specs=spec,
                               check_vma=False)  # pallas_call has no vma rule
        else:
            body = _embedded_body(pass_fn, ph, hl)
            bspec = P(ROWS_AXIS, BATCH_AXIS)
            sm = jax.shard_map(body, mesh=mesh, in_specs=bspec,
                               out_specs=bspec, check_vma=False)
        fn = _FN_CACHE[key] = jax.jit(sm)
    return fn


def _embed_windows(imgs: Array, ph: int, nr: int, hl: int) -> Array:
    """(n2, h2, w) -> (nr, n2, hl + 2*ph, w) overlapping row windows of the
    zero-padded image -- each shard's band with its halo embedded, the mesh
    analogue of the PR-3 batch fold's per-image zero halos."""
    padded = jnp.pad(imgs, ((0, 0), (ph, ph), (0, 0)))
    return jnp.stack([padded[:, i * hl: i * hl + hl + 2 * ph]
                      for i in range(nr)])


def sharded_call(pass_fn: Callable, pass_key: tuple, imgs: Array, ph: int, *,
                 devices: int | Sequence[int] | None = None,
                 mesh_shape: tuple[int, int] | None = None,
                 halo: str = "exchange") -> Array:
    """Run `pass_fn` (an (N, H, W) -> (N, H, W) map needing ph halo rows)
    sharded over a (batch, rows) mesh. `pass_key` must hash the pass's
    static identity (taps, method, ...) -- it keys the jit cache."""
    if halo not in HALO_MODES:
        raise ValueError(f"halo must be one of {HALO_MODES}, got {halo!r}")
    n, h, w = imgs.shape
    mesh = filter_mesh(devices, mesh_shape, n=n)
    nb, nr = mesh.devices.shape
    if nr == 1:
        # no row sharding -> no halo of either kind: run the plain pass per
        # batch shard (keeps the traced shape == `shard_local_shape` and
        # skips the embedded mode's host-side window copy)
        halo = "exchange"
    n2, h2, hl = shard_dims(n, h, nb, nr, ph)
    # §12 chaos hook: one probe per participating shard before dispatch --
    # a matching rule models that shard's host/device failing the whole
    # collective call (which is how a lost mesh member actually presents).
    # The key carries the shard's *global device id* (§13): a rule keyed
    # `dev<id>` models that one device dying, which is what lets the
    # elastic pool's per-device probe find the survivors
    # (repro.runtime.elastic.surviving_devices).
    traced = obs_trace.tracing()
    for shard, dev in enumerate(mesh.devices.flat):
        fault_probe(SITE_SHARD, key=f"{pass_key[0]}/{halo}/dev{dev.id}",
                    index=shard)
        if traced:
            # §15: one event per participating shard, on the same stream
            # as the request spans of the batch being dispatched
            obs_trace.emit("shard", filt=pass_key[0], halo=halo,
                           shard=shard, dev=dev.id, n=n)
    x = jnp.asarray(imgs)
    if n2 != n or h2 != h:
        x = jnp.pad(x, ((0, n2 - n), (0, h2 - h), (0, 0)))
    if halo == "embedded":
        win = _embed_windows(x, ph, nr, hl)
        out = _sharded_fn(pass_key, pass_fn, mesh, ph, halo, hl)(win)
        out = out.transpose(1, 0, 2, 3).reshape(n2, h2, w)
    else:
        out = _sharded_fn(pass_key, pass_fn, mesh, ph, halo, hl)(x)
    return out[:n, :h]


def _kw_key(kw: dict) -> tuple:
    return tuple(sorted(kw.items()))


def _taps_key(taps) -> tuple:
    a = np.asarray(taps)
    return (a.shape, tuple(a.reshape(-1).tolist()))


def sharded_conv2d_pass(imgs: Array, taps, *, devices: int | Sequence[int] | None = None,
                        mesh_shape: tuple[int, int] | None = None,
                        halo: str = "exchange", **kw) -> Array:
    """`repro.filters.conv.conv2d_pass` over the (batch, rows) mesh --
    bit-identical to the local pass (DESIGN.md §9). `kw` is forwarded."""
    from repro.filters.conv import conv2d_pass
    kh = int(np.shape(taps)[0])
    taps = np.asarray(taps)
    return sharded_call(lambda x: conv2d_pass(x, taps, **kw),
                        ("conv2d", _taps_key(taps), _kw_key(kw)),
                        jnp.asarray(imgs), kh // 2, devices=devices,
                        mesh_shape=mesh_shape, halo=halo)


def sharded_fused_separable_pass(imgs: Array, row, col, *,
                                 devices: int | Sequence[int] | None = None,
                                 mesh_shape: tuple[int, int] | None = None,
                                 halo: str = "exchange", **kw) -> Array:
    """`repro.filters.conv.fused_separable_pass` over the mesh."""
    from repro.filters.conv import fused_separable_pass
    row, col = np.asarray(row), np.asarray(col)
    kh = int(col.size)
    return sharded_call(lambda x: fused_separable_pass(x, row, col, **kw),
                        ("fused", _taps_key(row), _taps_key(col), _kw_key(kw)),
                        jnp.asarray(imgs), kh // 2, devices=devices,
                        mesh_shape=mesh_shape, halo=halo)


def _spec_key(spec: FilterSpec) -> tuple:
    return (spec.name, _taps_key(spec.taps), spec.shift, spec.post)


def sharded_apply_filter(imgs: Array, filt: FilterSpec | str, *,
                         devices: int | Sequence[int] | None = None,
                         mesh_shape: tuple[int, int] | None = None,
                         halo: str = "exchange", **kw) -> Array:
    """`repro.filters.apply_filter` over the (batch, rows) mesh.

    Accepts the same image shapes ((H, W), (N, H, W), (N, H, W, 1)) and
    filter keywords (method, nbits, separable, fused, mult_impl, block_*,
    interpret) as the local entry point and returns a bit-identical uint8
    batch. The per-shard pass resolves its block shapes from the
    shard-local shape (DESIGN.md §9)."""
    from repro.filters.pipeline import _normalize, _restore, apply_filter
    spec = get_filter(filt) if isinstance(filt, str) else filt
    arr, orig = _normalize(jnp.asarray(imgs))
    ph = int(spec.taps.shape[0]) // 2
    out = sharded_call(lambda x: apply_filter(x, spec, **kw),
                       ("filter", _spec_key(spec), _kw_key(kw)),
                       arr, ph, devices=devices, mesh_shape=mesh_shape,
                       halo=halo)
    return _restore(out, orig)


__all__ = ["HALO_MODES", "sharded_apply_filter", "sharded_call",
           "sharded_conv2d_pass", "sharded_fused_separable_pass"]
