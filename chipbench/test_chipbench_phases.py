"""The readers of the serve worker's phase spans and transfer counters
give the numbers their definitions say on a hand-built record, read a
real traced server's events, and stay silent on a run with nothing to
read (an untraced run, or a program without the spans or counters). CPU
only; libtpu is never loaded."""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np
import pytest

import harness

PHASE_METRICS = ("prep_ms", "upload_ms", "device_wait_ms", "fetch_ms")


def leaf(name, t0, t1, batch=None):
    ev = {"ts": t0, "event": "span", "span": name, "end": t1,
          "parent": None if batch is None else "dispatch",
          "thread": "repro-serve-worker"}
    if batch is not None:
        ev["batch"] = batch
    return ev


def synthetic(events, transfer_before=None, transfer_after=None):
    """Two batches in a window [10, 20]: batch 1 spends 1/2/4/8 ms in
    prep/upload/device_wait/fetch, batch 2 3/4/6/10 ms, batch 3 (before
    the window) 100 ms in each."""
    stats_before, stats = {}, {}
    if transfer_before is not None:
        stats_before["transfer"] = transfer_before
        stats["transfer"] = transfer_after
    return SimpleNamespace(events=events, t_start=10.0, t_last=20.0,
                           stats_before=stats_before, stats=stats)


def two_batches():
    evs = []
    for batch, t, ms in ((3, 5.0, (100, 100, 100, 100)),
                         (1, 11.0, (1, 2, 4, 8)), (2, 12.0, (3, 4, 6, 10))):
        for name, dur in zip(("prep", "upload", "device_wait", "fetch"), ms):
            evs.append(leaf(name, t, t + dur / 1e3, batch))
            t += dur / 1e3
    evs.append(leaf("idle", 13.0, 14.0))
    evs.append(leaf("collect", 14.0, 14.002))
    evs.append({"ts": 11.0, "event": "span", "span": "dispatch",
                "end": 11.5, "parent": "batch", "batch": 1})
    return evs


@pytest.mark.parametrize("metric,want", [
    ("prep_ms", 1.0), ("upload_ms", 2.0), ("device_wait_ms", 4.0),
    ("fetch_ms", 8.0)])
def test_phase_readers_take_the_median_batch_of_the_window(metric, want):
    # nearest-rank median of two batches is the lower one
    got = harness.load_reader(metric)(synthetic(two_batches()))
    assert got == pytest.approx(want)


def test_host_path_share_leaves_out_idle_and_device_wait():
    got = harness.load_reader("host_path_pct")(synthetic(two_batches()))
    busy = 0.001 + 0.002 + 0.008 + 0.003 + 0.004 + 0.010 + 0.002
    device = 0.004 + 0.006
    assert got == pytest.approx(100 * busy / (busy + device))


def test_h2d_bytes_per_pixel_is_the_counters_difference():
    run = synthetic([], {"h2d_bytes": {"": 400}, "pixels": {"": 100}},
                    {"h2d_bytes": {"": 400 + 4 * 800}, "pixels":
                     {"": 100 + 600}})
    assert harness.load_reader("h2d_bytes_per_px")(run) == pytest.approx(
        4 * 800 / 600)


@pytest.mark.parametrize("metric", PHASE_METRICS + ("host_path_pct",
                                                    "h2d_bytes_per_px"))
def test_readers_are_silent_with_nothing_to_read(metric):
    read = harness.load_reader(metric)
    assert read(synthetic(None)) is None          # untraced run
    # a program without the spans or the transfer counters
    older = synthetic([{"ts": 11.0, "event": "dispatch", "seq": 1}])
    assert read(older) is None


def test_readers_read_a_traced_server():
    from repro.serve import ImageFilterServer, ServerConfig

    h, w = 48, 64
    rng = np.random.default_rng(7)
    imgs = [rng.integers(0, 256, (h, w)).astype(np.uint8) for _ in range(6)]
    with ImageFilterServer(ServerConfig(max_batch=4, max_delay_ms=1.0,
                                        trace=True)) as srv:
        srv.warmup([(h, w)], ["gaussian5"], batches=(1, 2, 4))
        before = srv.stats()
        t_start = harness.clock()
        futs = [srv.submit(im, "gaussian5") for im in imgs]
        for f in futs:
            f.result(120)
        run = SimpleNamespace(events=srv.trace.events(), t_start=t_start,
                              t_last=harness.clock(), stats_before=before,
                              stats=srv.stats())
    for metric in PHASE_METRICS:
        assert harness.load_reader(metric)(run) > 0
    assert 0 < harness.load_reader("host_path_pct")(run) <= 100
    slots = sum((1 << max(0, n - 1).bit_length()) * c
                for n, c in run.stats["occupancy"].items())
    assert harness.load_reader("h2d_bytes_per_px")(run) == pytest.approx(
        4 * slots / len(imgs))
