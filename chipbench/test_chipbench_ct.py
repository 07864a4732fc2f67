"""The CT series cell: 12-bit slices in 16-bit containers through the whole
served path on the CPU, at the size the fault tests use. A sound run reads
`correct`; the control (an 8-bit row pass, which drops the slices' high
digits) answers wrongly and reads false; the cell's per-layer readers find
the program's 16/32-bit build and its device -> host bytes, and nothing
where a program does not count them."""
from __future__ import annotations

import json
from types import SimpleNamespace

import numpy as np

import harness
import loadgen
from test_chipbench_faults import TINY, run, tiny
from test_chipbench_harness import _files_only_repo

CELL = "ct512_12bit.series_backlog"


def test_the_ct_cell_serves_12_bit_pixels_in_16_bit_containers():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.config["bit_depth"] == 12
    assert harness.multiplier(cell) == {"method": "refmlm", "nbits": 16}
    assert harness.multiplier(cell, control=True) == {"method": "refmlm",
                                                      "nbits": 8}
    (kind,) = harness.resolve_kinds(cell)
    assert kind.shape == (512, 512) and kind.dtype == np.uint16
    assert [m["name"] for m in cell.per_layer] == [
        "conv_roofline.ct512", "tap_leaves_per_px.ct512",
        "d2h_bytes_per_px.ct512"]
    assert {m["name"] for m in cell.end_to_end} == {"mpix_per_s", "setup_s"}


def test_ct_sound_run_is_correct():
    line = run(tiny(CELL))
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["malformed"]["value"] == 0
    assert line["checks"]["mismatched_px"]["value"] == 0
    assert "setup_s" in line["metrics"] and "mpix_per_s" in line["metrics"]
    assert line["load"]["jaxpr_trace_duration_in_window"] == 0


def test_ct_control_is_not_correct():
    line = run(tiny(CELL), control=True)
    assert not line["correct"]
    assert line["checks"]["malformed"]["value"] == 0
    assert line["checks"]["mismatched_px"]["value"] > 0


SOBEL_X = {"sobel_x": {"row": [-1, 0, 1], "col": [1, 2, 1], "shift": 0,
                       "post": "abs"}}
GAUSSIAN5 = {"gaussian5": {"row": [1, 4, 6, 4, 1], "col": [1, 4, 6, 4, 1],
                           "shift": 8, "post": "clip"}}
PEAK = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def _ct12_cell(tmp_path, filters: dict) -> harness.Cell:
    """A 12-bit deployment of `filters` stored in 16-bit containers and
    served on the 16-bit multiplier, added as files and entries only."""
    repo, bench, spec = _files_only_repo(tmp_path)
    (bench / "configs" / "ct12.json").write_text(json.dumps({
        "name": "ct12", "bit_depth": 12,
        "images": {"slice": [24, 32]}, "distinct_images": 2,
        "filters": filters,
        "method": "refmlm", "control": {"method": "refmlm", "nbits": 8},
        "server": {"max_batch": 2, "max_delay_ms": 2.0},
        "warm_batches": [1, 2]}))
    (bench / "traffic" / "ct_backlog.json").write_text(json.dumps({
        "loop": "closed", "in_flight": 2, "check_sample": 8,
        "mix": [{"image": "slice", "filter": f, "share": 1}
                for f in filters]}))
    spec["configs"].append({"name": "ct12", "source": "new",
                            "file": "chipbench/configs/ct12.json",
                            "reduced": [], "why": "new"})
    spec["workloads"].append({"name": "ct12.backlog", "config": "ct12",
                              "traffic": "ct_backlog", "chips": 1,
                              "why": "new"})
    for m in spec["end_to_end"]:
        if m["name"] == "mpix_per_s":
            m["workloads"].append("ct12.backlog")
    (repo / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.load_cell("ct12.backlog", repo=repo, bench=bench)


def test_a_12_bit_smoothing_cell_is_correct_on_the_program(tmp_path):
    """The program answers 16-bit containers in uint16, so a 12-bit cell
    of gaussian5, which cannot pass 4095 on 12-bit pixels, reads `correct`
    with every answer well formed."""
    line = harness.run_cell(_ct12_cell(tmp_path, GAUSSIAN5), 2**32 + 3, 0.3,
                            peak=PEAK)
    assert line["correct"], line["checks"]
    assert line["checks"]["malformed"]["value"] == 0
    assert line["checks"]["mismatched_px"]["value"] == 0


def test_a_12_bit_derivative_cell_keeps_the_container_magnitude(tmp_path):
    """The program knows the container, not the stored bits: a Sobel
    magnitude on 12-bit slices is clipped at 65535, not at 4095 as the
    reference of a 12-bit deployment clips it. The answers are well formed
    and differ only where the magnitude passes 4095."""
    line = harness.run_cell(_ct12_cell(tmp_path, SOBEL_X), 2**32 + 3, 0.3,
                            peak=PEAK)
    assert not line["correct"]
    assert line["checks"]["malformed"]["value"] == 0
    assert line["checks"]["mismatched_px"]["value"] > 0


def _served(cell: harness.Cell, count: int):
    """A warmed server of the cell that served `count` requests: (record
    of what the readers see, the served requests)."""
    mult = harness.multiplier(cell)
    kinds, pools, srv = harness.prepare(cell, 2**33 + 7, mult)
    try:
        before = srv.stats()
        futs = [srv.submit(pools["slice"][i % len(pools["slice"])],
                           "gaussian5", **mult) for i in range(count)]
        outs = [f.result(600) for f in futs]
        stats = srv.stats()
    finally:
        srv.close()
    reqs = [loadgen.Request(i, 0, 0, 0.0) for i in range(count)]
    for r in reqs:
        r.done = 1.0
    record = SimpleNamespace(cell=cell, kinds=kinds, requests=reqs,
                             stats=stats, stats_before=before)
    return record, outs


def test_ct_readers_read_the_16_32_bit_build_and_the_uint16_fetch():
    cell = tiny(CELL)
    record, outs = _served(cell, 3)
    assert all(o.dtype == np.uint16 and o.shape == TINY for o in outs)
    leaves = harness.load_reader("tap_leaves_per_px.ct512")(record)
    # 8 pixel digits x 6 non-zero coefficient digits in the 16-bit row
    # pass; 16 x 6 in the 32-bit column pass less the 3 leaves of pixel
    # digit 15 at coefficient digit 1, whose weight 2**32 is 0 in uint32
    assert leaves == 48 + 93
    d2h = harness.load_reader("d2h_bytes_per_px.ct512")(record)
    before = record.stats_before["occupancy"]
    slots = sum((1 << max(0, n - 1).bit_length()) * (c - before.get(n, 0))
                for n, c in record.stats["occupancy"].items())
    # 2 bytes a pixel of every traced slot, pad images included
    assert slots >= 3 and d2h == 2.0 * slots / 3


def test_ct_readers_are_silent_on_a_program_that_counts_nothing():
    cell = tiny(CELL)
    empty = SimpleNamespace(cell=cell, kinds=harness.resolve_kinds(cell),
                            requests=[], stats={"transfer": {
                                "h2d_bytes": {"": 0}, "pixels": {"": 0}}},
                            stats_before={"transfer": {
                                "h2d_bytes": {"": 0}, "pixels": {"": 0}}})
    assert harness.load_reader("d2h_bytes_per_px.ct512")(empty) is None
    assert harness.load_reader("tap_leaves_per_px.ct512")(empty) is None
