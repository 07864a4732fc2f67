"""Serving 16-bit containers (DESIGN.md §5, §10): a uint16 payload is
admitted, batched, dispatched and answered in uint16, bit-identical to the
plain int64 reference; uint8 and uint16 requests never share a batch; every
8-bit request keeps its bucket key and its answer, bit for bit; a request
the datapath cannot serve in 16 bits is refused at admission."""
import numpy as np
import pytest

from repro.filters.ref import plain_filter_ref
from repro.serve import ImageFilterServer, ServerConfig
from repro.serve.executor import BatchExecutor, warm_container
from repro.serve.request import bucket_key, serve_key

FAR = 3600_000.0
SHAPE = (24, 20)


def image(seed: int, top: int, dtype) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, top + 1, SHAPE).astype(dtype)


@pytest.mark.parametrize("top", [4095, 65535])
def test_uint16_request_is_answered_in_uint16(top):
    imgs = [image(i, top, np.uint16) for i in range(3)]
    with ImageFilterServer(ServerConfig(max_batch=4,
                                        max_delay_ms=FAR)) as srv:
        futs = [srv.submit(im, "gaussian5", nbits=16) for im in imgs]
        srv.close()
        outs = [f.result(120) for f in futs]
        transfer = srv.stats()["transfer"]
    for im, out in zip(imgs, outs):
        assert out.dtype == np.uint16 and out.shape == SHAPE
        np.testing.assert_array_equal(out, plain_filter_ref(im, "gaussian5"))
    h, w = SHAPE
    # one batch of 3 traced as 4, fetched at 2 bytes a pixel
    assert transfer["d2h_bytes"] == {"uint16": 2 * 4 * h * w}
    assert transfer["pixels"] == {"": 3 * h * w}


def test_uint8_and_uint16_requests_never_share_a_batch():
    u8 = [image(10 + i, 255, np.uint8) for i in range(2)]
    u16 = [image(20 + i, 4095, np.uint16) for i in range(2)]
    with ImageFilterServer(ServerConfig(max_batch=8,
                                        max_delay_ms=FAR)) as srv:
        futs = [srv.submit(im, "box3", nbits=16) for pair in zip(u8, u16)
                for im in pair]
        srv.close()
        outs = [f.result(120) for f in futs]
        stats = srv.stats()
    assert stats["batches"] == 2 and stats["occupancy"] == {2: 2}
    assert [o.dtype for o in outs] == [np.uint8, np.uint16] * 2
    for im, out in zip([im for p in zip(u8, u16) for im in p], outs):
        np.testing.assert_array_equal(out, plain_filter_ref(im, "box3"))
    assert stats["transfer"]["d2h_bytes"] == {"uint8": 2 * 24 * 20,
                                              "uint16": 2 * 2 * 24 * 20}


def test_uint8_bucket_keys_and_answers_are_unchanged():
    assert (bucket_key("gaussian5", "refmlm", "local", 8, 24, 20)
            == "gaussian5/refmlm/local/b8/24x20/normal")
    assert (bucket_key("gaussian5", "refmlm", "local", 16, 24, 20,
                       container="uint16")
            == "gaussian5/refmlm/local/b16/24x20/normal/uint16")
    assert (bucket_key("mlp", "refmlm", "local", 8, 8, 8,
                       workload="infer")
            == "mlp/refmlm/local/b8/8x8/normal/infer")
    imgs = [image(30 + i, 255, dt)
            for i, dt in enumerate((np.uint8, np.int32, np.int64))]
    with ImageFilterServer(ServerConfig(max_batch=4,
                                        max_delay_ms=FAR)) as srv:
        futs = [srv.submit(im, "sobel_y") for im in imgs]
        srv.close()
        outs = [f.result(120) for f in futs]
    for im, out in zip(imgs, outs):
        assert out.dtype == np.uint8
        np.testing.assert_array_equal(
            out, plain_filter_ref(im.astype(np.uint8), "sobel_y"))


@pytest.mark.parametrize("method,kw,match", [
    ("mitchell", {}, "'mitchell' has no 32-bit multiplier"),
    ("odma", {}, "'odma' has no 32-bit multiplier"),
    ("refmlm", {"exec": "sharded"}, "uint8 pixels only"),
    ("refmlm", {"exec": "streamed"}, "uint8 pixels only"),
])
def test_uint16_request_the_datapath_cannot_serve_is_refused(method, kw,
                                                             match):
    with ImageFilterServer(ServerConfig(max_batch=2,
                                        max_delay_ms=FAR)) as srv:
        with pytest.raises(ValueError, match=match):
            srv.submit(image(40, 4095, np.uint16), "gaussian5",
                       method=method, nbits=16, **kw)
        assert srv.stats()["submitted"] == 0


def test_warmup_at_16_bits_compiles_the_uint16_container():
    assert warm_container(8) == "uint8" and warm_container(16) == "uint16"
    assert warm_container(16, exec_mode="sharded") == "uint8"
    assert warm_container(16, workload="infer") == "uint8"
    ex = BatchExecutor()
    key = ex.warm(SHAPE, "gaussian5", nbits=16, n=2)
    assert key == serve_key(bucket_key("gaussian5", "refmlm",
                                       "local", 16, *SHAPE,
                                       container="uint16"), 2)
    with ImageFilterServer(ServerConfig(max_batch=2,
                                        max_delay_ms=FAR)) as srv:
        srv.warmup([SHAPE], ["gaussian5"], nbits=16, batches=(2,))
        futs = [srv.submit(image(50 + i, 4095, np.uint16), "gaussian5",
                           nbits=16) for i in range(2)]
        outs = [f.result(120) for f in futs]
        compile_stats = srv.stats()["compile"]
    assert all(o.dtype == np.uint16 for o in outs)
    assert compile_stats["misses"] == 0 and compile_stats["hits"] == 1
