"""Distribution invariance for the filter datapath (`repro.distribute`,
DESIGN.md §9): sharded and streamed execution must be bit-identical to the
local path for every bank filter and multiplier config, across device
counts, mesh shapes, halo modes and tile shapes -- including non-divisible
row counts, non-divisible batches and images smaller than one shard.

Anything needing more than one device runs in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the pattern of
tests/test_distribution.py -- the main process must keep seeing 1 device).
Streamed mode, the tile planner, the cache-keying contract and the
1-device mesh run in-process.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from repro.distribute import (  # noqa: E402
    apply_filter as dist_apply_filter,
    auto_mesh_shape,
    plan_tiles,
    shard_dims,
    shard_local_shape,
    stream_filter,
)
from repro.filters import FILTER_NAMES, apply_filter  # noqa: E402
from repro.tuning import config_key, invalidate_cache, store_cache  # noqa: E402

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

RNG = np.random.default_rng(7)
BATCH = jnp.asarray(RNG.integers(0, 256, (2, 48, 40)), jnp.int32)

#: the multipliers of the invariance contract: exact, the paper's REFMLM
#: recursion, and Mitchell's approximate multiplier.
MULT_METHODS = ("exact", "refmlm", "mitchell")


def run_sub(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


# ----------------------------------------------------------------- planning

class TestPlanTiles:
    @pytest.mark.parametrize("h,w,th,tw,ph,pw", [
        (48, 40, 16, 16, 2, 2), (50, 37, 16, 24, 1, 1), (5, 17, 8, 8, 2, 2),
        (100, 100, 100, 100, 2, 2), (33, 1, 7, 1, 1, 1),
    ])
    def test_invariants(self, h, w, th, tw, ph, pw):
        tiles = plan_tiles(h, w, th, tw, ph, pw)
        owned = np.zeros((h, w), np.int32)
        for t in tiles:
            owned[t.r0:t.r1, t.c0:t.c1] += 1
            # the source window is the owned window dilated by the halo,
            # clipped to the image, with pad_* restoring the clipped part
            assert t.sr0 == max(0, t.r0 - ph) and t.sr1 == min(h, t.r1 + ph)
            assert t.sc0 == max(0, t.c0 - pw) and t.sc1 == min(w, t.c1 + pw)
            assert t.pad_top == t.sr0 - (t.r0 - ph) >= 0
            assert t.pad_left == t.sc0 - (t.c0 - pw) >= 0
            # padded windows all fit the uniform (th + 2ph, tw + 2pw) batch
            assert t.pad_top + (t.sr1 - t.sr0) <= th + 2 * ph
            assert t.pad_left + (t.sc1 - t.sc0) <= tw + 2 * pw
        assert (owned == 1).all(), "output pixels must be owned exactly once"

    def test_bad_tile_raises(self):
        with pytest.raises(ValueError):
            plan_tiles(8, 8, 0, 4, 1, 1)


class TestShardPlanning:
    def test_auto_mesh_prefers_batch(self):
        assert auto_mesh_shape(8, 32) == (8, 1)
        assert auto_mesh_shape(8, 4) == (4, 2)
        assert auto_mesh_shape(8, 1) == (1, 8)
        assert auto_mesh_shape(6, 4) == (3, 2)

    def test_shard_dims_pads_to_mesh(self):
        assert shard_dims(3, 50, 2, 4, 2) == (4, 52, 13)
        assert shard_dims(1, 5, 1, 8, 2) == (1, 16, 2)   # smaller than shard
        assert shard_dims(2, 48, 1, 1, 2) == (2, 48, 48)

    def test_shard_local_shape_never_global(self):
        """The tuning-cache key under sharding is the shard-local band with
        its halo (DESIGN.md §9), not the global image shape."""
        assert shard_local_shape(2, 48, 40, 1, 4, 2) == (2, 16, 40)
        assert shard_local_shape(2, 48, 40, 2, 1, 2) == (1, 48, 40)
        assert shard_local_shape(32, 128, 128, 8, 1, 2) == (4, 128, 128)


# ------------------------------------------------------------------ streamed

class TestStreamed:
    @pytest.mark.parametrize("name", FILTER_NAMES)
    @pytest.mark.parametrize("method", MULT_METHODS)
    def test_bit_identical_to_local(self, name, method):
        local = apply_filter(BATCH, name, method=method)
        got = apply_filter(BATCH, name, method=method,
                           exec="streamed", tile=(16, 16), tile_batch=5)
        np.testing.assert_array_equal(np.asarray(local), got)

    @pytest.mark.parametrize("tile", [(8, 8), (16, 24), (48, 40), (64, 64),
                                      (13, 9)])
    def test_tile_shape_invariance(self, tile):
        local = np.asarray(apply_filter(BATCH, "gaussian5"))
        got = apply_filter(BATCH, "gaussian5", exec="streamed", tile=tile)
        np.testing.assert_array_equal(local, got)

    def test_single_image_and_nhwc(self):
        img = BATCH[0]
        local = np.asarray(apply_filter(img, "sobel_x"))
        got = apply_filter(img, "sobel_x", exec="streamed", tile=(16, 16))
        assert got.shape == local.shape
        np.testing.assert_array_equal(local, got)
        nhwc = BATCH[..., None]
        got4 = apply_filter(nhwc, "sobel_x", exec="streamed", tile=(16, 16))
        assert got4.shape == nhwc.shape

    def test_memmap_source_and_out(self, tmp_path):
        """The out-of-core contract: both endpoints can be disk-backed."""
        h, w = 96, 80
        src_path, out_path = tmp_path / "src.u8", tmp_path / "out.u8"
        data = RNG.integers(0, 256, (h, w)).astype(np.uint8)
        np.memmap(src_path, np.uint8, "w+", shape=(h, w))[:] = data
        src = np.memmap(src_path, np.uint8, "r", shape=(h, w))
        out = np.memmap(out_path, np.uint8, "w+", shape=(h, w))
        res = stream_filter(src, "gaussian3", method="refmlm",
                            tile=(32, 32), out=out)
        assert res is out
        out.flush()
        local = np.asarray(apply_filter(jnp.asarray(data, jnp.int32),
                                        "gaussian3", method="refmlm"))
        np.testing.assert_array_equal(
            local, np.memmap(out_path, np.uint8, "r", shape=(h, w)))

    def test_out_shape_mismatch_raises(self):
        with pytest.raises(ValueError, match="out shape"):
            stream_filter(np.zeros((8, 8), np.uint8), "gaussian3",
                          out=np.zeros((4, 4), np.uint8))

    def test_out_aliasing_src_raises(self):
        """In-place streaming would read back already-written output
        through the halo overlap -- must be refused, not silently wrong."""
        buf = np.asarray(RNG.integers(0, 256, (32, 32)), np.uint8)
        with pytest.raises(ValueError, match="alias"):
            stream_filter(buf, "gaussian3", tile=(8, 8), out=buf)
        with pytest.raises(ValueError, match="alias"):
            stream_filter(buf[None], "gaussian3", tile=(8, 8), out=buf[None])

    def test_exec_arg_validation(self):
        with pytest.raises(ValueError, match="exec must be one of"):
            apply_filter(BATCH, "gaussian3", exec="remote")
        with pytest.raises(ValueError, match="require exec="):
            apply_filter(BATCH, "gaussian3", tile=(8, 8))
        with pytest.raises(ValueError, match="require exec="):
            apply_filter(BATCH, "gaussian3", halo="embedded")
        with pytest.raises(ValueError, match="sharded-mode"):
            apply_filter(BATCH, "gaussian3", exec="streamed", devices=2)
        with pytest.raises(ValueError, match="sharded-mode"):
            apply_filter(BATCH, "gaussian3", exec="streamed", halo="embedded")
        with pytest.raises(ValueError, match="streamed-mode"):
            apply_filter(BATCH, "gaussian3", exec="sharded", tile=(8, 8))
        with pytest.raises(ValueError, match="streamed-mode"):
            apply_filter(BATCH, "gaussian3", exec="sharded", tile_batch=4)


# ------------------------------------------------- sharded (1 device, local)

class TestShardedOneDevice:
    """Device count 1: the mesh degenerates to (1, 1) but the whole
    shard_map + halo plumbing still runs (the {1} point of the device-count
    invariance axis; {2, 8} run in the subprocess below)."""

    @pytest.mark.parametrize("name", ["gaussian5", "laplacian"])
    @pytest.mark.parametrize("halo", ["exchange", "embedded"])
    def test_bit_identical_to_local(self, name, halo):
        local = np.asarray(apply_filter(BATCH, name))
        got = np.asarray(apply_filter(BATCH, name, exec="sharded",
                                      mesh_shape=(1, 1), halo=halo))
        np.testing.assert_array_equal(local, got)

    def test_sharded_first_then_local(self):
        """Regression: a kernel whose taps are first baked INSIDE the
        shard_map trace must leave nothing traced behind -- a cached
        tracer would poison every later local call with the same
        (method, taps) key (UnexpectedTracerError). Uses a multiplier
        config no other test touches so every cache is cold."""
        got = apply_filter(BATCH, "sharpen3", method="mitchell_ecc3",
                           exec="sharded", mesh_shape=(1, 1))
        local = apply_filter(BATCH, "sharpen3", method="mitchell_ecc3")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(local))

    def test_mirror_defaults_to_sharded(self):
        local = np.asarray(apply_filter(BATCH, "box3"))
        got = np.asarray(dist_apply_filter(BATCH, "box3", mesh_shape=(1, 1)))
        np.testing.assert_array_equal(local, got)

    def test_too_many_devices_raises(self):
        with pytest.raises(ValueError, match="are visible"):
            apply_filter(BATCH, "gaussian3", exec="sharded", mesh_shape=(2, 4))

    def test_bad_halo_raises(self):
        with pytest.raises(ValueError, match="halo must be one of"):
            apply_filter(BATCH, "gaussian3", exec="sharded",
                         mesh_shape=(1, 1), halo="telepathy")


# -------------------------------------------------------------- cache keying

@pytest.fixture()
def tmp_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
    invalidate_cache()
    yield tmp_path
    invalidate_cache()


class TestDistributedCacheKeying:
    """Under exec != 'local' the block-shape cache must be consulted with
    the per-tile / per-shard shape the pass actually traces with -- a
    winner cached for the GLOBAL image shape must never be inherited
    (DESIGN.md §9 satellite)."""

    SENTINEL = 104          # a valid but distinctive block_rows

    def _recording(self, monkeypatch):
        import repro.filters.conv as conv
        calls = []
        real = conv.resolve_blocks

        def spy(kind, n, h, w, kh, kw, **kwargs):
            cfg = real(kind, n, h, w, kh, kw, **kwargs)
            calls.append(((n, h, w), cfg))
            return cfg

        monkeypatch.setattr(conv, "resolve_blocks", spy)
        return calls

    def test_streamed_ignores_global_shape_winner(self, tmp_cache, monkeypatch):
        n, h, w = BATCH.shape
        store_cache({config_key("fused", n, h, w, 5, 5):
                     {"block_rows": self.SENTINEL, "block_cols": None,
                      "batch_fold": True, "us_per_call": 1.0}})
        calls = self._recording(monkeypatch)
        got = apply_filter(BATCH, "gaussian5", exec="streamed", tile=(16, 16))
        assert calls, "streamed mode must consult the cache per tile batch"
        for shape, cfg in calls:
            assert shape != (n, h, w), \
                "tile batch looked the cache up with the GLOBAL image shape"
            assert cfg.block_rows != self.SENTINEL, \
                "a global-shape winner leaked into a tile batch"
        np.testing.assert_array_equal(
            np.asarray(apply_filter(BATCH, "gaussian5")), got)

    def test_streamed_honors_tile_shape_winner(self, tmp_cache, monkeypatch):
        # gaussian5 / tile 16x16 / batch 5 -> fused passes on (5, 20, 20)
        store_cache({config_key("fused", 5, 20, 20, 5, 5):
                     {"block_rows": 16, "block_cols": None,
                      "batch_fold": True, "us_per_call": 1.0}})
        calls = self._recording(monkeypatch)
        apply_filter(BATCH, "gaussian5", exec="streamed", tile=(16, 16),
                     tile_batch=5)
        hits = [cfg for shape, cfg in calls if shape == (5, 20, 20)]
        assert hits and all(c.block_rows == 16 for c in hits), \
            "a tile-local-shape winner must be picked up by tile batches"

    def test_sharded_keys_on_shard_local_shape(self, tmp_cache, monkeypatch):
        """One-device mesh: the pass keys on what `shard_local_shape` names
        (degenerate here -- the (1, 1) mesh's local shape IS the global
        one). The real multi-shard assertion, with a poisoned global-shape
        winner, runs in the subprocess sweep below."""
        calls = self._recording(monkeypatch)
        # a shape no other test shards, so the jitted-executor cache cannot
        # satisfy the call without re-tracing (and re-resolving blocks)
        fresh = jnp.asarray(RNG.integers(0, 256, (2, 44, 36)), jnp.int32)
        apply_filter(fresh, "gaussian5", exec="sharded", mesh_shape=(1, 1))
        n, h, w = fresh.shape
        assert calls
        assert all(shape == shard_local_shape(n, h, w, 1, 1, 2)
                   for shape, _ in calls)


# ------------------------------------------------- sharded (2 and 8 devices)

def test_sharded_multi_device_sweep():
    """The heavyweight invariance sweep at device counts {2, 8}: every bank
    filter x multiplier config on a (2, 4) mesh with non-divisible batch
    and rows; mesh-shape / halo-mode / device-count variations, images
    smaller than one shard, the raw pass wrappers, and the shard-local
    cache-keying assertion -- all in one subprocess (one JAX init)."""
    out = run_sub("""
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.distribute import (sharded_conv2d_pass,
                                      sharded_fused_separable_pass,
                                      shard_local_shape)
        from repro.filters import FILTER_NAMES, apply_filter
        from repro.filters.conv import conv2d_pass, fused_separable_pass

        assert len(jax.devices()) == 8
        rng = np.random.default_rng(7)
        imgs = jnp.asarray(rng.integers(0, 256, (3, 50, 40)), jnp.int32)

        def check(name, local, **kw):
            got = np.asarray(apply_filter(imgs, name, exec="sharded", **kw))
            assert (got == np.asarray(local)).all(), (name, kw)

        # every bank filter x {exact, refmlm, mitchell} on a (2, 4) mesh:
        # batch 3 over 2 shards and 50 rows over 4 shards, both non-divisible
        for name in FILTER_NAMES:
            for method in ("exact", "refmlm", "mitchell"):
                local = apply_filter(imgs, name, method=method)
                check(name, local, method=method, mesh_shape=(2, 4))
        print("bank x mult sweep ok")

        # mesh shapes, halo modes, device counts {2, 8}
        local5 = apply_filter(imgs, "gaussian5")
        for ms in ((8, 1), (1, 8), (4, 2), (2, 1), (1, 2)):
            check("gaussian5", local5, mesh_shape=ms)
        for halo in ("exchange", "embedded"):
            check("gaussian5", local5, mesh_shape=(2, 4), halo=halo)
            check("gaussian5", local5, mesh_shape=(1, 8), halo=halo)
        check("gaussian5", local5, devices=2)       # auto mesh over 2 devices
        check("gaussian5", local5, devices=8)
        locl = apply_filter(imgs, "laplacian")
        check("laplacian", locl, mesh_shape=(1, 8), halo="embedded")
        print("mesh/halo/device-count variations ok")

        # image smaller than one shard: 5 rows over 8 row shards
        tiny = jnp.asarray(rng.integers(0, 256, (1, 5, 17)), jnp.int32)
        lt = np.asarray(apply_filter(tiny, "gaussian5"))
        for halo in ("exchange", "embedded"):
            gt = np.asarray(apply_filter(tiny, "gaussian5", exec="sharded",
                                         mesh_shape=(1, 8), halo=halo))
            assert (gt == lt).all(), halo
        print("smaller-than-one-shard ok")

        # the raw pass wrappers
        taps = np.outer([1, 4, 6, 4, 1], [1, 4, 6, 4, 1])
        lc = np.asarray(conv2d_pass(imgs, taps, method="refmlm"))
        sc = np.asarray(sharded_conv2d_pass(imgs, taps, method="refmlm",
                                            mesh_shape=(1, 4)))
        assert (sc == lc).all()
        r = np.array([1, 4, 6, 4, 1])
        lf = np.asarray(fused_separable_pass(imgs, r, r, nbits2=16))
        sf = np.asarray(sharded_fused_separable_pass(imgs, r, r, nbits2=16,
                                                     mesh_shape=(2, 2)))
        assert (sf == lf).all()
        print("pass wrappers ok")

        # cache keying: poison the cache with a winner for the GLOBAL image
        # shape; every resolve_blocks call under sharding must see the
        # shard-local shape, never the global (3, 52, 44), and never
        # inherit the poisoned winner (DESIGN.md SS9 satellite)
        import os, tempfile
        os.environ["REPRO_TUNE_CACHE"] = tempfile.mkdtemp()
        from repro.tuning import config_key, invalidate_cache, store_cache
        SENTINEL = 104
        store_cache({config_key("fused", 3, 52, 44, 5, 5):
                     {"block_rows": SENTINEL, "block_cols": None,
                      "batch_fold": True, "us_per_call": 1.0}})
        import repro.filters.conv as conv
        calls = []
        real = conv.resolve_blocks
        def spy(kind, n, h, w, kh, kw, **kwargs):
            cfg = real(kind, n, h, w, kh, kw, **kwargs)
            calls.append(((n, h, w), cfg))
            return cfg
        conv.resolve_blocks = spy
        fresh = jnp.asarray(rng.integers(0, 256, (3, 52, 44)), jnp.int32)
        got = np.asarray(apply_filter(fresh, "gaussian5", exec="sharded",
                                      mesh_shape=(2, 4)))
        conv.resolve_blocks = real
        del os.environ["REPRO_TUNE_CACHE"]
        invalidate_cache()
        want = shard_local_shape(3, 52, 44, 2, 4, 2)
        assert calls and all(s == want for s, _ in calls), (calls, want)
        assert all(cfg.block_rows != SENTINEL for _, cfg in calls), \
            "a global-shape winner leaked into a shard"
        assert (got == np.asarray(apply_filter(fresh, "gaussian5"))).all()
        print("shard-local cache keying ok")
    """)
    for marker in ("bank x mult sweep ok", "mesh/halo/device-count variations ok",
                   "smaller-than-one-shard ok", "pass wrappers ok",
                   "shard-local cache keying ok"):
        assert marker in out
