"""A served batch crosses to the device in its pixel container (DESIGN.md
§5, §10): uint8 and uint16 images are uploaded as they are and widened to
int32 inside the jitted conv passes, every other dtype keeps the int32
batch. Either way the answers are byte-identical, the transfer counter
prices the bytes actually uploaded, and a warmed bucket serves on the
executables its warm-up compiled."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.distribute import stream_filter
from repro.filters import FILTER_NAMES, apply_filter
from repro.filters import conv
from repro.filters.bank import get_filter
from repro.filters.pipeline import apply_filter_batch, batch_dtype
from repro.serve import ImageFilterServer, ServerConfig

FAR = 3600_000.0
SHAPE = (12, 20)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

#: dataflow -> the `apply_filter` arguments that force it
DATAFLOWS = {"fused": dict(separable=True, fused=True),
             "two_pass": dict(separable=True, fused=False),
             "direct": dict(separable=False)}

#: every jitted conv pass, whose traces are the compiled executables
JITTED_PASSES = (conv._conv2d_recurse, conv._fused_sep_recurse)


def batch(seed: int, top: int, dtype, n: int = 4) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, top + 1, (n, *SHAPE)).astype(dtype)


def cases(filters):
    return [(f, df) for f in filters for df in DATAFLOWS
            if df == "direct" or get_filter(f).separable]


def assert_narrow_matches_int32(imgs: np.ndarray, filt: str, **kw) -> None:
    """`imgs` (4 images in their container) against the same values as
    int32: the direct call, the served batch of 4, and 3 served images
    padded to 4 (one traced batch size, so one executable a dtype)."""
    container = imgs.dtype.name
    ref = np.asarray(apply_filter(imgs.astype(np.int32), filt,
                                  container=container, **kw))
    assert ref.dtype == imgs.dtype
    np.testing.assert_array_equal(np.asarray(apply_filter(imgs, filt, **kw)),
                                  ref)
    for pad_to, n in ((None, 4), (4, 3)):
        outs = apply_filter_batch(list(imgs[:n]), filt, pad_to=pad_to, **kw)
        assert len(outs) == n
        for out, want in zip(outs, ref):
            assert out.dtype == imgs.dtype
            np.testing.assert_array_equal(out, want)


@pytest.mark.parametrize("method", ["refmlm", "mitchell", "exact"])
@pytest.mark.parametrize("filt,dataflow", cases(FILTER_NAMES))
def test_uint8_batch_matches_int32(filt, dataflow, method):
    assert_narrow_matches_int32(batch(1, 255, np.uint8), filt, method=method,
                                **DATAFLOWS[dataflow])


@pytest.mark.parametrize("method", ["refmlm", "mitchell"])
@pytest.mark.parametrize("filt,dataflow",
                         cases(["sobel_x"]) + [("gaussian5", "fused")])
def test_uint8_batch_matches_int32_with_baked_taps(filt, dataflow, method):
    """The compiled backends' tap products (baked taps), on a second seed
    of pixels."""
    assert_narrow_matches_int32(batch(2, 255, np.uint8), filt, method=method,
                                **DATAFLOWS[dataflow])


@pytest.mark.parametrize("filt,dataflow", cases(["gaussian5", "sobel_x"]))
def test_uint16_batch_matches_int32(filt, dataflow):
    assert_narrow_matches_int32(batch(3, 65535, np.uint16), filt, nbits=16,
                                **DATAFLOWS[dataflow])


@pytest.mark.parametrize("dtypes,wire", [
    ((np.uint8,) * 3, np.uint8),
    ((np.uint16,) * 3, np.uint16),
    ((np.int32,) * 3, np.int32),
    ((np.int64,) * 3, np.int32),
    ((np.uint8, np.int32, np.uint8), np.int32),
])
def test_the_uploaded_batch_is_in_its_wire_dtype(monkeypatch, dtypes, wire):
    uploaded = []
    real = jax.device_put

    def spy(x, *args, **kwargs):
        uploaded.append((x.dtype, x.shape))
        return real(x, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", spy)
    imgs = [batch(4 + i, 255, dt, n=1)[0] for i, dt in enumerate(dtypes)]
    assert batch_dtype(imgs) == wire
    outs = apply_filter_batch(imgs, "box3", pad_to=4)
    assert uploaded == [(np.dtype(wire), (4, *SHAPE))]
    answer = np.uint16 if wire is np.uint16 else np.uint8
    assert all(o.dtype == answer for o in outs)


def test_an_int32_pixel_above_255_does_not_wrap():
    """An int32 pixel past the 8-bit range reaches the datapath as it is,
    not cut to its low byte on the way. The exact multiplier takes the
    whole int32 value, so a box3 of 300 differs from one of 44; the 8-bit
    recursion reads only the low 8 bits of its operand."""
    img = np.full(SHAPE, 300, np.int32)
    served = apply_filter_batch([img], "box3", method="exact")[0]
    np.testing.assert_array_equal(
        served, np.asarray(apply_filter(img, "box3", method="exact")))
    wrapped = apply_filter_batch([img.astype(np.uint8)], "box3",
                                 method="exact")[0]
    assert not np.array_equal(served, wrapped)


@pytest.mark.parametrize("dtype,top,nbits,itemsize", [
    (np.uint8, 255, 8, 1), (np.uint16, 4095, 16, 2), (np.int32, 255, 8, 4)])
def test_h2d_bytes_price_the_uploaded_dtype(dtype, top, nbits, itemsize):
    h, w = SHAPE
    imgs = batch(5, top, dtype, n=3)
    with ImageFilterServer(ServerConfig(max_batch=4,
                                        max_delay_ms=FAR)) as srv:
        futs = [srv.submit(im, "box3", nbits=nbits) for im in imgs]
        srv.close()
        [f.result(120) for f in futs]
        transfer = srv.stats()["transfer"]
    # one batch of 3 traced as 4: the pad slot crosses too
    assert transfer["h2d_bytes"] == {"": itemsize * 4 * h * w}
    assert transfer["pixels"] == {"": 3 * h * w}


def compiled() -> int:
    return sum(f._cache_size() for f in JITTED_PASSES)


@pytest.mark.parametrize("dtype,top,nbits", [(np.uint8, 255, 8),
                                              (np.uint16, 4095, 16)])
def test_a_warmed_bucket_serves_without_a_compile(dtype, top, nbits):
    with ImageFilterServer(ServerConfig(max_batch=2,
                                        max_delay_ms=FAR)) as srv:
        srv.warmup([SHAPE], ["gaussian5"], nbits=nbits, batches=(2,))
        warmed = compiled()
        futs = [srv.submit(im, "gaussian5", nbits=nbits)
                for im in batch(6, top, dtype, n=2)]
        outs = [f.result(120) for f in futs]
    assert all(o.dtype == dtype for o in outs)
    assert compiled() == warmed


def test_streamed_matches_local_on_a_uint8_batch():
    imgs = batch(7, 255, np.uint8)
    local = np.asarray(apply_filter(imgs, "gaussian5"))
    np.testing.assert_array_equal(
        stream_filter(imgs, "gaussian5", tile=(8, 8), tile_batch=4), local)
    outs = apply_filter_batch(list(imgs[:3]), "gaussian5", pad_to=4,
                              exec="streamed", tile=(8, 8))
    for out, want in zip(outs, local):
        np.testing.assert_array_equal(out, want)


def test_sharded_matches_local_on_a_uint8_batch():
    """Four virtual devices, in a subprocess (the main process keeps one)."""
    code = """
        import numpy as np
        from repro.filters import apply_filter
        from repro.filters.pipeline import apply_filter_batch
        rng = np.random.default_rng(8)
        imgs = rng.integers(0, 256, (4, 16, 20)).astype(np.uint8)
        for filt in ("gaussian5", "sharpen3"):
            local = np.asarray(apply_filter(imgs, filt))
            for halo in ("exchange", "embedded"):
                out = apply_filter(imgs, filt, exec="sharded", devices=4,
                                   mesh_shape=(2, 2), halo=halo)
                assert out.dtype == np.uint8, out.dtype
                np.testing.assert_array_equal(np.asarray(out), local)
            outs = apply_filter_batch(list(imgs[:3]), filt, pad_to=4,
                                      exec="sharded", devices=4,
                                      mesh_shape=(2, 2))
            for out, want in zip(outs, local):
                np.testing.assert_array_equal(out, want)
        print("ok")
    """
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    run = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert run.returncode == 0, run.stderr[-4000:]
    assert run.stdout.strip() == "ok"
