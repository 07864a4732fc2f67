"""repro.infer: calibration, routed forward, bit-identity, error report,
and the served-inference byte-equality contract (DESIGN.md §14)."""
import numpy as np
import pytest

from repro.data.images import inference_batch
from repro.infer import (InferWorkload, MODELS, calibrate, error_report,
                         export_scales, forward, format_report, init_params,
                         with_scales)
from repro.serve import ImageFilterServer, ServerConfig
from repro.serve.request import bucket_key

HW = (8, 8)


@pytest.fixture(scope="module")
def cal_models():
    out = {}
    for name, build in MODELS.items():
        g = build(HW)
        p = init_params(g, seed=1)
        out[name] = calibrate(g, p, inference_batch(4, HW, seed=100))
    return out


@pytest.fixture(scope="module")
def x_eval():
    return inference_batch(8, HW, seed=0)


# ------------------------------------------------------------- bit identity
@pytest.mark.parametrize("model", sorted(MODELS))
@pytest.mark.parametrize("method", ["refmlm", "refmlm_kom3",
                                    "schoolbook_int16", "karatsuba_int16"])
def test_exact_methods_bit_identical_to_oracle(cal_models, x_eval, model,
                                               method):
    """The paper's zero-error theorem lifted to networks: refmlm (and the
    exact limb decompositions) produce logits byte-equal to the
    exact-quantized int8 oracle, end to end."""
    cal = cal_models[model]
    oracle, o_accs = forward(cal, x_eval, "int8", collect=True)
    got, accs = forward(cal, x_eval, method, collect=True)
    for a, o in zip(accs, o_accs):
        assert np.array_equal(np.asarray(a), np.asarray(o))
    assert np.array_equal(np.asarray(got), np.asarray(oracle))


def test_approx_methods_drift_but_stay_close(cal_models, x_eval):
    """Mitchell drifts (nonzero ulp), ECC shrinks the drift, and the
    report orders them that way."""
    cal = cal_models["cnn"]
    rep = error_report(cal, x_eval, ("mitchell", "mitchell_ecc2", "refmlm"))
    assert rep["refmlm"]["layers"][0]["max_ulp"] == 0
    m1 = rep["mitchell"]["layers"][-1]["max_ulp"]
    m2 = rep["mitchell_ecc2"]["layers"][-1]["max_ulp"]
    assert m1 > m2 > 0
    assert rep["mitchell_ecc2"]["psnr_db"] > rep["mitchell"]["psnr_db"]
    text = format_report(rep, title="t")
    assert "mitchell_ecc2" in text and "PSNR" in text


# -------------------------------------------------------------- calibration
def test_static_scale_export_round_trip(cal_models, x_eval):
    cal = cal_models["mlp"]
    bundle = export_scales(cal)
    g = MODELS["mlp"](HW)
    rebuilt = with_scales(g, init_params(g, seed=1), bundle)
    assert np.array_equal(np.asarray(forward(rebuilt, x_eval, "int8")),
                          np.asarray(forward(cal, x_eval, "int8")))


def test_calibration_rejects_non_finite(cal_models):
    g = MODELS["mlp"](HW)
    p = init_params(g, seed=1)
    bad = np.full((2, *HW), np.inf, dtype=np.float32)
    with pytest.raises(ValueError, match="calibration overflow"):
        calibrate(g, p, bad)


def test_per_layer_pinning(cal_models, x_eval):
    """A per-layer method map routes each layer independently; pinning
    every layer to the oracle recovers oracle bytes."""
    cal = cal_models["mlp"]
    oracle = np.asarray(forward(cal, x_eval, "int8"))
    mixed = np.asarray(forward(cal, x_eval, "mitchell",
                               per_layer={1: "int8", 2: "int8"}))
    assert np.array_equal(mixed, oracle)
    with pytest.raises(ValueError, match="invalid pinned method"):
        forward(cal, x_eval, "int8", per_layer={1: "exact"})


# ------------------------------------------------------------------ serving
def test_bucket_keys_separate_workloads():
    filt = bucket_key("gaussian3", "refmlm", "local", 8, 8, 8)
    inf = bucket_key("mlp", "refmlm", "local", 8, 8, 8,
                     workload="infer")
    assert not filt.endswith("/infer")
    assert inf.endswith("/infer")
    assert inf.split("/")[2] == "local"      # pool._native_mode contract


@pytest.mark.parametrize("max_batch", [1, 3, 8])
def test_served_inference_byte_equal_direct(cal_models, x_eval, max_batch):
    """Any flush size: served logits == direct forward bytes, per row."""
    cfg = ServerConfig(max_batch=max_batch, max_delay_ms=5.0,
                       workloads={"infer": InferWorkload(cal_models)})
    with ImageFilterServer(cfg) as srv:
        futs = [srv.submit(x_eval[i], "cnn", method="refmlm",
                           workload="infer")
                for i in range(len(x_eval))]
        outs = np.stack([f.result(60) for f in futs])
        stats = srv.stats()
    direct = np.asarray(forward(cal_models["cnn"], x_eval, "refmlm"))
    assert np.array_equal(outs, direct)
    assert stats["served"] == len(x_eval)
    if max_batch > 1:
        assert any(n > 1 for n in stats["occupancy"])


def test_mixed_workloads_one_server(cal_models, x_eval):
    """Filter and infer traffic interleave in one server without sharing
    buckets, and both return direct-call bytes."""
    from repro.data.images import fingerprint
    from repro.filters.pipeline import apply_filter
    img = fingerprint((16, 16), seed=3)
    cfg = ServerConfig(max_batch=4, max_delay_ms=5.0,
                       workloads={"infer": InferWorkload(cal_models)})
    with ImageFilterServer(cfg) as srv:
        ffut = srv.submit(img, "gaussian3", method="refmlm")
        ifuts = [srv.submit(x_eval[i], "mlp", method="mitchell_ecc2",
                            workload="infer") for i in range(4)]
        fout = ffut.result(60)
        iouts = np.stack([f.result(60) for f in ifuts])
    assert np.array_equal(fout, np.asarray(apply_filter(img, "gaussian3",
                                                        method="refmlm")))
    assert np.array_equal(
        iouts, np.asarray(forward(cal_models["mlp"], x_eval[:4],
                                  "mitchell_ecc2")))


def test_infer_validation_fails_fast(cal_models):
    cfg = ServerConfig(workloads={"infer": InferWorkload(cal_models)})
    x = inference_batch(1, HW, seed=0)[0]
    with ImageFilterServer(cfg) as srv:
        with pytest.raises(ValueError, match="unknown infer model"):
            srv.submit(x, "nope", workload="infer")
        with pytest.raises(ValueError, match="method"):
            srv.submit(x, "mlp", method="exact", workload="infer")
        with pytest.raises(ValueError, match="local"):
            srv.submit(x, "mlp", exec="sharded", workload="infer")
        with pytest.raises(ValueError, match="expects one"):
            srv.submit(np.zeros((4, 4), np.float32), "mlp", workload="infer")
        with pytest.raises(ValueError, match="unknown workload"):
            srv.submit(x, "mlp", workload="training")


def test_infer_warmup_precompiles(cal_models):
    cfg = ServerConfig(workloads={"infer": InferWorkload(cal_models)})
    with ImageFilterServer(cfg) as srv:
        keys = srv.warmup([(8, 8)], filters=["mlp", "cnn"],
                          methods=["refmlm"], batches=(1, 4),
                          workload="infer")
        assert len(keys) == 4
        assert all(k.endswith("/n1") or k.endswith("/n4") for k in keys)
        assert all("/infer/" in k for k in keys)
        x = inference_batch(2, HW, seed=7)
        futs = [srv.submit(x[i], "mlp", method="refmlm", workload="infer")
                for i in range(2)]
        [f.result(60) for f in futs]
        stats = srv.stats()
    assert stats["compile"]["warmed"] >= 4
