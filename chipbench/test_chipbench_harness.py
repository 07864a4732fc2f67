"""The harness finds its cells by name, a new cell needs only new files,
the statistics are what they claim, the schedules are seeded, and the
command refuses a machine without the chips and peaks it needs. CPU only;
libtpu is never loaded."""
from __future__ import annotations

import json
import math
import os
import re
import shutil
import subprocess
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import harness
import loadgen
import run as run_cli

CHIPBENCH = Path(__file__).resolve().parent
REPO = CHIPBENCH.parent
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_each_cell_resolves_its_files_by_name(cell):
    c = harness.load_cell(cell)
    assert c.config["name"] == next(w["config"] for w in SPEC["workloads"]
                                    if w["name"] == cell)
    kinds = harness.resolve_kinds(c)
    assert kinds and all(k.pixels > 0 for k in kinds)
    names = [m["name"] for m in c.end_to_end + c.per_layer]
    assert "setup_s" in names
    for name in names:
        assert callable(harness.load_reader(name))


def test_benchmark_json_keeps_to_its_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "chipbench/run.py"]
    assert SPEC["paths"] == ["chipbench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    configs = {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (REPO / c["file"]).is_file()
        assert c["name"] in {w["config"] for w in SPEC["workloads"]}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (CHIPBENCH / "traffic" / f"{w['traffic']}.json").is_file()
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"])
        assert m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert set(m.get("workloads", [])) <= cells
        assert (CHIPBENCH / "metrics" / f"{m['name']}.py").is_file()
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        moved = next(x for x in SPEC["end_to_end"] if x["name"] == m["moves"])
        # every cell a per-layer metric lists reports the metric it moves
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
    for cell in cells:
        reported = [m for m in SPEC["end_to_end"] if harness.applies(m, cell)]
        assert len(reported) >= 2
        assert any(harness.applies(m, cell) for m in SPEC["per_layer"])


def test_a_new_cell_is_new_files_only(tmp_path):
    """A later change adds a traffic mix, a metric and a cell as files and
    entries; the harness's code is not touched."""
    repo = tmp_path / "repo"
    bench = repo / "chipbench"
    shutil.copytree(CHIPBENCH / "configs", bench / "configs")
    shutil.copytree(CHIPBENCH / "traffic", bench / "traffic")
    shutil.copytree(CHIPBENCH / "metrics", bench / "metrics")
    spec = json.loads(json.dumps(SPEC))
    (bench / "traffic" / "verify_burst.json").write_text(json.dumps({
        "loop": "burst", "mix": [{"image": "DB2", "filter": "sobel_x",
                                  "share": 1}],
        "period_ms": 10.0, "burst": 4, "jitter_ms": 0.5}))
    (bench / "metrics" / "answered_share.py").write_text(
        "def read(run):\n"
        "    return 100.0 * sum(r.ok for r in run.requests) / "
        "len(run.requests)\n")
    spec["workloads"].append({"name": "fvc2004.verify_burst",
                              "config": "fvc2004", "traffic": "verify_burst",
                              "chips": 1, "why": "new"})
    spec["per_layer"].append({"name": "answered_share", "unit": "%",
                              "better": "higher", "source": "program_counter",
                              "layer": "serve", "moves": "latency_p95_ms",
                              "workloads": ["fvc2004.verify_burst"]})
    (repo / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("fvc2004.verify_burst", repo=repo, bench=bench)
    assert cell.traffic["burst"] == 4
    assert [m["name"] for m in cell.per_layer] == ["answered_share"]
    read = harness.load_reader("answered_share", bench=bench)
    reqs = [SimpleNamespace(ok=True), SimpleNamespace(ok=False)]
    assert read(SimpleNamespace(requests=reqs)) == 50.0
    kinds = harness.resolve_kinds(cell)
    assert kinds[0].shape == (364, 328) and kinds[0].ksize == (3, 3)


def _files_only_repo(tmp_path) -> tuple[Path, Path, dict]:
    """A copy of the benchmark's data files and spec under `tmp_path`."""
    repo = tmp_path / "repo"
    bench = repo / "chipbench"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(CHIPBENCH / sub, bench / sub)
    return repo, bench, json.loads(json.dumps(SPEC))


def _ct12_cell(tmp_path) -> harness.Cell:
    """A 12-bit deployment stored in 16-bit containers and served on the
    16-bit multiplier, added as files and entries only."""
    repo, bench, spec = _files_only_repo(tmp_path)
    (bench / "configs" / "ct12.json").write_text(json.dumps({
        "name": "ct12", "bit_depth": 12,
        "images": {"slice": [24, 32]}, "distinct_images": 2,
        "filters": {"sobel_x": {"row": [-1, 0, 1], "col": [1, 2, 1],
                                "shift": 0, "post": "abs"}},
        "method": "refmlm", "control": {"method": "refmlm", "nbits": 8},
        "server": {"max_batch": 2, "max_delay_ms": 2.0},
        "warm_batches": [1, 2]}))
    (bench / "traffic" / "ct_backlog.json").write_text(json.dumps({
        "loop": "closed", "in_flight": 2, "check_sample": 8,
        "mix": [{"image": "slice", "filter": "sobel_x", "share": 1}]}))
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


def test_a_12_bit_cell_is_new_files_only(tmp_path):
    """Pixels wider than 8 bits come from the configuration alone: pools,
    reference, answer dtype, roofline bytes and multiplier width."""
    import inputs
    import kernelwork
    from reference import filter_ref

    cell = _ct12_cell(tmp_path)
    assert harness.multiplier(cell) == {"method": "refmlm", "nbits": 16}
    (kind,) = harness.resolve_kinds(cell)
    assert kind.dtype == np.uint16 and kind.bytes_per_px == 2
    ops, nbytes = kernelwork.filter_work(kind.pixels, *kind.ksize,
                                         kind.bytes_per_px)
    assert nbytes == 4 * kind.pixels and ops == 18 * kind.pixels

    pools = inputs.image_pools(cell.config["images"], 2, 2**33 + 1,
                               cell.config["bit_depth"])
    pool = pools["slice"]
    assert pool.dtype == np.uint16 and pool.shape == (2, 24, 32)
    assert pool.max() == 4095 and (pool > 255).any()

    ramp = np.broadcast_to(np.arange(32, dtype=np.uint16) * 128, (24, 32))
    out = filter_ref(ramp, kind.spec, bit_depth=12)
    assert out.dtype == np.uint16
    assert out[5, 5] == 4 * 256               # inside: kept above 255
    assert out[5, 31] == 4095                 # 4 x 3840 at the edge: clipped
    assert (filter_ref(pool[0], kind.spec, bit_depth=12) > 255).any()

    answers = harness.Answers([kind], 4, seed=1)
    answers(loadgen.Request(0, 0, 0, 0.0), np.zeros((24, 32), np.uint8))
    answers(loadgen.Request(1, 0, 0, 0.0), np.zeros((24, 32), np.uint16))
    assert answers.malformed == 1 and answers.seen == 1


def test_a_12_bit_cell_on_an_8_bit_program_is_not_correct(tmp_path):
    """Today's program answers in uint8 whatever the pixels' width, so a
    12-bit cell run on it reads `correct` false."""
    cell = _ct12_cell(tmp_path)
    line = harness.run_cell(cell, 2**32 + 3, 0.3,
                            peak={"int8_ops_per_s": 393e12,
                                  "hbm_bytes_per_s": 819e9})
    assert not line["correct"]
    assert line["checks"]["malformed"]["value"] > 0


def test_a_method_without_a_reference_is_refused(tmp_path):
    repo, bench, spec = _files_only_repo(tmp_path)
    cfg = json.loads((bench / "configs" / "fvc2004.json").read_text())
    (bench / "configs" / "fvc2004.json").write_text(
        json.dumps(dict(cfg, method="babic_bb1")))
    (repo / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(harness.CellError, match="babic_bb1"):
        harness.load_cell("fvc2004.enrol_backlog", repo=repo, bench=bench)


@pytest.mark.parametrize("malloc", [{"arena_max": 2},
                                    {"trim_threshold": 1 << 31}])
def test_a_mix_names_only_known_malloc_settings(tmp_path, malloc):
    repo, bench, spec = _files_only_repo(tmp_path)
    mix = json.loads((bench / "traffic" / "enrol_backlog.json").read_text())
    (bench / "traffic" / "enrol_backlog.json").write_text(
        json.dumps(dict(mix, malloc=malloc)))
    (repo / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(harness.CellError, match="malloc"):
        harness.load_cell("fvc2004.enrol_backlog", repo=repo, bench=bench)


def test_the_enrolment_mix_sets_the_allocator_before_set_up():
    """Both enrolment cells keep freed buffers for reuse; the setting is
    applied in a process of its own, as `run.py` applies it."""
    for name in ("fvc2004.enrol_backlog", "fvc2004_mitchell.enrol_backlog"):
        assert harness.load_cell(name).traffic["malloc"] == {
            "mmap_threshold": 1 << 25, "trim_threshold": (1 << 31) - 1,
            "top_pad": 1 << 28}
    code = ("import harness; harness.set_malloc(harness.load_cell("
            "'fvc2004.enrol_backlog').traffic['malloc']); print('set')")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], cwd=CHIPBENCH,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0 and proc.stdout.strip() == "set", proc.stderr


#: sha256 over the four first cells' image pools (2 images of each shape
#: at seed 2**33 + 7), the reference output of each kind on the second
#: image, and each kind's `filter_work`, as the 8-bit harness made them.
EIGHT_BIT_DIGEST = ("18a1f816145b9201cbcd1d694045125966c2619d3d8e0d0d"
                    "36097e04386626b0")


def test_8_bit_pools_references_and_work_are_unchanged():
    import hashlib

    import inputs
    import kernelwork
    from reference import filter_ref

    digest = hashlib.sha256()
    for name in ("fvc2004.enrol_backlog", "fvc2004.verify_open",
                 "camera1080.stream", "fvc2004_pool4.verify_open"):
        cell = harness.load_cell(name)
        cfg = cell.config
        kinds = harness.resolve_kinds(cell)
        names = sorted({k.image for k in kinds})
        pools = inputs.image_pools({n: cfg["images"][n] for n in names}, 2,
                                   2**33 + 7, cfg["bit_depth"])
        for n in names:
            p = pools[n]
            digest.update(f"{name}/{n}/{p.dtype}/{p.shape}".encode())
            digest.update(p.tobytes())
        for k in kinds:
            out = filter_ref(pools[k.image][1], k.spec, method=cfg["method"],
                             bit_depth=cfg["bit_depth"])
            digest.update(f"{k.filter}/{out.dtype}/{out.shape}".encode())
            digest.update(out.tobytes())
            digest.update(repr(kernelwork.filter_work(
                k.pixels, *k.ksize, k.bytes_per_px)).encode())
    assert digest.hexdigest() == EIGHT_BIT_DIGEST


def test_unknown_cell_is_refused():
    with pytest.raises(harness.CellError):
        harness.load_cell("no_such.cell")


def test_nearest_rank_percentiles():
    xs = list(range(1, 101))
    assert harness.nearest_rank(xs, 95) == 95
    assert harness.nearest_rank(xs, 50) == 50
    assert harness.nearest_rank(list(range(1, 21)), 95) == 19
    assert harness.nearest_rank([7.0], 95) == 7.0
    assert harness.nearest_rank([3, 1, 2], 100) == 3
    with pytest.raises(ValueError):
        harness.nearest_rank([], 50)


def test_p95_reader_takes_every_request_from_its_due_instant():
    read = harness.load_reader("latency_p95_ms")
    t0 = 100.0
    reqs = [loadgen.Request(i, 0, 0, due=i * 0.01, sent=t0 + i * 0.01,
                            done=t0 + i * 0.01 + (i + 1) * 1e-3)
            for i in range(40)]
    reqs[5].error = "DeadlineExceeded: shed"       # failed: still counted
    run = SimpleNamespace(requests=reqs, t_start=t0)
    run.latencies_ms = lambda: harness.RunRecord.latencies_ms(run)
    lat = sorted((i + 1) * 1.0 for i in range(40))
    assert read(run) == pytest.approx(harness.nearest_rank(lat, 95))
    assert read(run) == pytest.approx(38.0)


@pytest.mark.parametrize("loop", ["open", "burst"])
def test_schedule_is_deterministic_in_the_seed(loop):
    traffic = json.loads((CHIPBENCH / "traffic" / "verify_open.json")
                         .read_text())
    traffic.update(loop=loop, rate_per_s=300, period_ms=20.0, burst=3,
                   jitter_ms=1.0)
    big = 2**33 + 12345                      # seeds beyond 32 bits
    a = loadgen.schedule(traffic, 4.0, 32, big)
    b = loadgen.schedule(traffic, 4.0, 32, big)
    c = loadgen.schedule(traffic, 4.0, 32, big + 1)
    key = [(r.due, r.kind, r.image) for r in a]
    assert key == [(r.due, r.kind, r.image) for r in b]
    assert key != [(r.due, r.kind, r.image) for r in c]
    # another seed: the same work in another order
    assert len(a) == len(c)
    gaps_a = np.diff([0.0] + [r.due for r in a])
    gaps_c = np.diff([0.0] + [r.due for r in c])
    if loop == "open":
        assert sorted(gaps_a) == pytest.approx(sorted(gaps_c), abs=1e-9)
    for seq in (a, c):
        counts = np.bincount([r.kind for r in seq], minlength=4)
        assert counts.max() - counts.min() <= 1
        assert all(0 <= r.due <= 4.0 + 0.01 for r in seq)
    if loop == "open":
        assert len(a) == 1200
        assert gaps_a.mean() == pytest.approx(1 / 300, rel=0.01)
    else:
        assert len(a) == 200 * 3
        assert sorted(r.due for r in a) == pytest.approx(
            sorted(r.due for r in c), abs=2e-3)


def test_closed_tables_are_seeded_and_balanced():
    traffic = json.loads((CHIPBENCH / "traffic" / "enrol_backlog.json")
                         .read_text())
    k1, i1 = loadgen.closed_tables(traffic, 32, 9)
    k2, i2 = loadgen.closed_tables(traffic, 32, 9)
    assert (k1 == k2).all() and (i1 == i2).all()
    counts = np.bincount(k1)
    assert counts.max() - counts.min() <= 1
    assert np.bincount(i1).max() - np.bincount(i1).min() <= 1


def test_exact_counts():
    assert loadgen.exact_counts([1, 1, 1], 10) in ([4, 3, 3], [3, 4, 3],
                                                   [3, 3, 4])
    assert sum(loadgen.exact_counts([2, 1], 7)) == 7


class _Future:
    def __init__(self, value, delay):
        self._value, self._at = value, time.monotonic() + delay

    def done(self):
        return time.monotonic() >= self._at

    def result(self, timeout=None):
        assert self.done()
        if isinstance(self._value, Exception):
            raise self._value
        return self._value


def test_lateness_counts_in_latency():
    """A sender held up in submit sends late; the latency of every request
    still runs from its due instant."""
    reqs = [loadgen.Request(i, 0, 0, due=i * 0.002) for i in range(20)]
    answered = []

    def submit(req):
        time.sleep(0.01)                     # slower than the schedule
        if req.idx == 3:
            raise RuntimeError("refused")
        if req.idx == 7:
            return _Future(ValueError("bad image"), 0.001)
        return _Future(req.idx, 0.001)

    t0 = time.monotonic()
    loadgen.run_scheduled(reqs, submit, lambda r, out: answered.append(out),
                          t0)
    late = [r.sent - (t0 + r.due) for r in reqs]
    assert late[-1] > 0.1                    # 20 * 8 ms behind by the end
    for r in reqs:
        assert r.done - (t0 + r.due) >= r.sent - (t0 + r.due)
    assert not reqs[3].ok and reqs[3].error.startswith("RuntimeError")
    assert not reqs[7].ok and reqs[7].error.startswith("ValueError")
    assert sorted(answered) == [i for i in range(20) if i not in (3, 7)]
    # answers are stamped within a poll interval (and a scheduler tick)
    for r in reqs:
        if r.ok:
            assert r.done - r.sent < 0.001 + 0.02


def test_closed_loop_keeps_its_clients_busy_until_the_end():
    tables = (np.zeros(16, int), np.arange(16) % 4)
    inflight = []
    lock = threading.Lock()
    peak = [0]

    def submit(req):
        with lock:
            inflight.append(req.idx)
            peak[0] = max(peak[0], len(inflight))
        fut = _Future(req.idx, 0.005)

        class Done:
            done = fut.done

            def result(self, timeout=None):
                out = fut.result()
                with lock:
                    inflight.remove(req.idx)
                return out
        return Done()

    t0 = time.monotonic()
    reqs = loadgen.run_closed(tables, 3, submit, lambda r, o: None, t0,
                              t0 + 0.2)
    assert peak[0] == 3
    assert all(r.ok for r in reqs) and len(reqs) > 20
    assert [r.idx for r in reqs] == list(range(len(reqs)))
    assert all(r.due == pytest.approx(r.sent - t0) for r in reqs)


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"))
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    proc = subprocess.run(
        [sys.executable, str(CHIPBENCH / "run.py"), "--workload",
         "fvc2004.enrol_backlog", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


class _FakeDevice:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_run_exits_nonzero_on_an_unknown_device_kind(monkeypatch, capsys,
                                                     tmp_path):
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(jax, "devices", lambda: [_FakeDevice("TPU v99")])
    rc = run_cli.main(["--workload", "fvc2004.enrol_backlog", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "not in peaks.json" in out.err
    assert out.out == ""


def test_check_device_counts_chips(monkeypatch):
    import jax
    peaks = {"TPU v5 lite": {}}
    monkeypatch.setattr(jax, "devices", lambda: [_FakeDevice("TPU v5 lite")])
    assert len(harness.check_device(1, peaks)) == 1
    with pytest.raises(harness.DeviceError, match="needs 4 chips"):
        harness.check_device(4, peaks)


def test_run_refuses_an_unknown_cell(capsys, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert run_cli.main(["--workload", "nope", "--seed", "1", "--seconds",
                         "1"]) != 0
    assert capsys.readouterr().out == ""


def test_answers_reservoir_is_bounded_and_checks_shape():
    kind = harness.Kind("DB1", "gaussian5", {}, (4, 6))
    answers = harness.Answers([kind], 8, seed=3)
    good = np.zeros((4, 6), np.uint8)
    for i in range(100):
        answers(loadgen.Request(i, 0, 0, 0.0), good)
    answers(loadgen.Request(100, 0, 0, 0.0), np.zeros((4, 6), np.int32))
    answers(loadgen.Request(101, 0, 0, 0.0), np.zeros((6, 4), np.uint8))
    assert len(answers.sample) == 8 and answers.seen == 100
    assert answers.malformed == 2
    assert len({r.idx for r, _ in answers.sample}) == 8
    assert not math.isnan(answers.sample[0][0].due)
