"""The trace reduction and the roofline arithmetic, on a trace recorded on
a TPU v5 lite (0.1 s of fvc2004.enrol_backlog traffic) and on synthetic
events. CPU only; nothing here needs a chip."""
from __future__ import annotations

import gzip
import json
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

import devtrace
import kernelwork

FIXTURE = Path(__file__).resolve().parent / "testdata" / \
    "enrol_window.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("xplane") / "enrol.xplane.pb"
    with gzip.open(FIXTURE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return devtrace.load_xplane(str(path))


def test_recorded_trace_has_one_tpu_and_host_events(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    assert len(recorded.devices["/device:TPU:0"]) == 126
    assert len(recorded.host) > 1000
    s, e = devtrace.event_span(recorded, "chipbench.window")
    assert 0 < e - s < 1_000_000_000


def test_recorded_trace_reduces_to_its_numbers(recorded):
    red = devtrace.reduce_trace(
        recorded, devtrace.load_kernel_patterns(),
        window=devtrace.event_span(recorded, "chipbench.window"))
    assert red.window_s == pytest.approx(0.231280313)
    assert red.busy_s == pytest.approx(0.068465971)
    assert red.idle_share == pytest.approx(1 - 0.068465971 / 0.231280313)
    # the Pallas conv kernels take all but the pads, slices and casts
    assert red.kernel_s["conv"] == pytest.approx(0.067961535)
    assert red.unmatched_share == pytest.approx(0.007367689, rel=1e-6)
    assert red.device_ops[0] == ["_fused_sep_recurse s32[8,480,768]",
                                 pytest.approx(0.049861293)]
    assert len(red.device_ops) == devtrace.TOP
    assert len(red.idle_gaps) == devtrace.TOP
    gaps = [g[1] for g in red.idle_gaps]
    assert gaps == sorted(gaps, reverse=True)
    assert red.idle_gaps[0] == ["np.asarray(jax.Array)",
                                pytest.approx(0.027738975)]


def test_reduction_is_json_serialisable(recorded):
    red = devtrace.reduce_trace(recorded, devtrace.load_kernel_patterns())
    json.dumps({"device_ops": red.device_ops, "idle_gaps": red.idle_gaps})
    assert red.window_s > 0.231


def _synthetic():
    ops = {
        "/device:TPU:0": [
            ('%_fused_sep_recurse.1 = s32[8,8]{1,0} custom-call(), '
             'custom_call_target="tpu_custom_call"', 100, 300),
            ("%slice.0 = s32[8,8]{1,0} slice()", 250, 350),
            ("%pad.3 = s32[8,8]{1,0} pad()", 600, 700),
        ],
        "/device:TPU:1": [
            ('%_fused_sep_recurse.2 = s32[8,8]{1,0} custom-call(), '
             'custom_call_target="tpu_custom_call"', 0, 500),
        ],
    }
    host = [("thread-long", 0, 1000), ("chipbench.submit", 350, 600),
            ("np.asarray(jax.Array)", 400, 560), ("DevicePut", 700, 1000)]
    return devtrace.Trace(ops, host)


def test_synthetic_busy_idle_and_kernels():
    red = devtrace.reduce_trace(_synthetic(), devtrace.load_kernel_patterns(),
                                window=(0, 1000))
    # TPU:0 busy [100, 350) + [600, 700) = 350; TPU:1 busy 500; mean 425
    assert red.busy_s == pytest.approx(425e-9)
    assert red.idle_share == pytest.approx(1 - 425 / 1000)
    assert red.kernel_s["conv"] == pytest.approx(700e-9)
    assert red.op_s == pytest.approx(900e-9)
    assert red.unmatched_share == pytest.approx(200 / 900)
    assert red.device_ops[0] == ["_fused_sep_recurse s32[8,8]",
                                 pytest.approx(700e-9)]


def test_synthetic_gaps_prefer_program_events():
    red = devtrace.reduce_trace(_synthetic(), {"conv": ["tpu_custom_call"]},
                                window=(0, 1000))
    gaps = {round(sec * 1e9): label for label, sec in red.idle_gaps}
    # TPU:0 gaps [0,100), [350,600), [700,1000); TPU:1 gap [500,1000)
    assert sorted(gaps) == [100, 250, 300, 500]
    # np.asarray overlaps [350, 600) less than chipbench.submit does, and
    # still labels it: the program's events come first
    assert gaps[250] == "np.asarray(jax.Array)"
    assert gaps[300] == "DevicePut"
    assert gaps[100] == "no host event"


def test_merge_and_op_family():
    assert devtrace.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3),
                                                                (5, 9)]
    assert devtrace.op_family(
        "%convert_element_type.1 = u8[8,480,640]{2,1,0:T(8,128)(4,1)} "
        "convert(...)") == "convert_element_type u8[8,480,640]"
    assert devtrace.op_family("plain name") == "plain name"


def test_no_device_ops_is_an_error():
    with pytest.raises(ValueError):
        devtrace.reduce_trace(devtrace.Trace({}, [("x", 0, 1)]), {})


def test_peaks_table_is_keyed_by_device_kind():
    peaks = kernelwork.load_peaks()
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["bf16_flops_per_s"] == 197e12
    assert "source" in v5e


@pytest.mark.parametrize("k,bound", [(3, "bytes"), (5, "bytes")])
def test_filter_work_is_bytes_bound(k, bound):
    peak = kernelwork.load_peaks()["TPU v5 lite"]
    ops, nbytes = kernelwork.filter_work(480 * 640, k, k)
    assert ops == 2 * k * k * 480 * 640
    assert nbytes == 2 * 480 * 640
    t, which = kernelwork.least_time(ops, nbytes, peak)
    assert which == bound
    assert t == pytest.approx(nbytes / 819e9)


def test_least_time_can_be_ops_bound():
    t, which = kernelwork.least_time(1e12, 1.0, {"int8_ops_per_s": 1e12,
                                                 "hbm_bytes_per_s": 1e12})
    assert (t, which) == (1.0, "ops")


def test_roofline_pct_counts_answered_requests_only():
    kind = SimpleNamespace(pixels=1000, ksize=(5, 5), bytes_per_px=1)
    reqs = [SimpleNamespace(ok=True, kind=0), SimpleNamespace(ok=False, kind=0)]
    peak = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    run = SimpleNamespace(requests=reqs, kinds=[kind], peak=peak,
                          device=SimpleNamespace(kernel_s={"conv": 1e-6}))
    want = 100 * (2000 / 819e9) / 1e-6
    assert kernelwork.roofline_pct(run, "conv") == pytest.approx(want)
    kind.bytes_per_px = 2                    # 16-bit containers: twice
    assert kernelwork.roofline_pct(run, "conv") == pytest.approx(2 * want)
    kind.bytes_per_px = 1
    run.device.kernel_s = {"conv": 0.0}
    assert kernelwork.roofline_pct(run, "conv") is None
    run.device = None
    assert kernelwork.roofline_pct(run, "conv") is None
