"""The benchmark's harness: one cell of `BENCHMARK.json`, run once.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name `BENCHMARK.json` gives it:

- `configs/<config>.json` -- the deployment: image shapes and bit depth,
  filters with their taps, method, server settings, chips;
- `traffic/<mix>.json` -- parameters of the one general generator
  (`loadgen.py`);
- `metrics/<metric>.py` -- a reader `read(run) -> float | None` over the
  run's record (`RunRecord`); None leaves the metric out of the line.

A run builds one `ImageFilterServer`, warms up the cell's own shapes,
filters and batch sizes, drives the traffic through `submit` for the
window, drains, and then checks a seeded sample of the answers against the
plain reference of the configuration's method and bit depth
(`reference.py`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import random
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

import devtrace
import inputs
import loadgen
from reference import CORRELATE, filter_ref, kernel_size, pixel_dtype

CHIPBENCH = Path(__file__).resolve().parent
REPO = CHIPBENCH.parent
SPEC_FILE = REPO / "BENCHMARK.json"
SRC = REPO / "src"

clock = time.monotonic

#: host trace event spanning the window and its drain in traced runs.
WINDOW_EVENT = "chipbench.window"


class CellError(Exception):
    """The cell, its configuration or its traffic cannot be found or used."""


class DeviceError(Exception):
    """The machine lacks what the cell needs: a TPU, enough chips, peaks
    for its device kind, or the allocator settings its traffic names."""


# ------------------------------------------------------------------ spec
@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list[dict]
    per_layer: list[dict]


def load_spec(path: Path = SPEC_FILE) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec: dict | None = None, *, repo: Path = REPO,
              bench: Path = CHIPBENCH) -> Cell:
    """Resolve a cell by name: its configuration and traffic files and the
    metrics that apply to it."""
    spec = load_spec(repo / "BENCHMARK.json") if spec is None else spec
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise CellError(f"no cell {name!r} in BENCHMARK.json; cells: "
                        f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if cell["config"] not in configs:
        raise CellError(f"cell {name!r} names unknown config {cell['config']!r}")
    config_file = repo / configs[cell["config"]]["file"]
    traffic_file = bench / "traffic" / f"{cell['traffic']}.json"
    for path in (config_file, traffic_file):
        if not path.is_file():
            raise CellError(f"cell {name!r}: missing {path}")
    with open(config_file, encoding="utf-8") as f:
        config = json.load(f)
    with open(traffic_file, encoding="utf-8") as f:
        traffic = json.load(f)
    if config["method"] not in CORRELATE:
        raise CellError(f"cell {name!r}: config {cell['config']!r} names "
                        f"method {config['method']!r}, which has no plain "
                        f"reference; methods with one: {sorted(CORRELATE)}")
    try:
        pixel_dtype(config["bit_depth"])
    except ValueError as err:
        raise CellError(f"cell {name!r}: {err}") from None
    for key, value in traffic.get("malloc", {}).items():
        if key not in MALLOPT or not 0 <= int(value) < 1 << 31:
            raise CellError(f"cell {name!r}: traffic {cell['traffic']!r} "
                            f"sets malloc {key}={value!r}; known: "
                            f"{sorted(MALLOPT)}, each 0 to 2**31 - 1")
    return Cell(name, config, traffic, int(cell["chips"]),
                [m for m in spec["end_to_end"] if applies(m, name)],
                [m for m in spec["per_layer"] if applies(m, name)])


#: glibc `mallopt` parameters a traffic mix may set under "malloc".
MALLOPT = {"trim_threshold": -1, "top_pad": -2, "mmap_threshold": -3}


def set_malloc(params: dict) -> None:
    """Apply a traffic mix's allocator settings to this process through
    glibc's `mallopt`, before set-up allocates; DeviceError where the C
    library lacks it or refuses one."""
    import ctypes

    libc = ctypes.CDLL(None)
    if params and not hasattr(libc, "mallopt"):
        raise DeviceError("the traffic sets malloc parameters, and this "
                          "C library has no mallopt")
    for key, value in params.items():
        if libc.mallopt(MALLOPT[key], int(value)) != 1:
            raise DeviceError(f"mallopt refused {key}={value}")


def load_reader(metric: str, *, bench: Path = CHIPBENCH):
    """The `read` function of `metrics/<metric>.py`."""
    path = bench / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise CellError(f"metric {metric!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def check_device(chips: int, peaks: dict):
    """The devices a cell runs on, or DeviceError: JAX must find a TPU, at
    least `chips` of them, and their kind must be in the peaks table."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise DeviceError(f"needs a TPU; JAX found {platform!r} devices")
    if len(devices) < chips:
        raise DeviceError(f"needs {chips} chips; JAX found {len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise DeviceError(f"device kind {kind!r} is not in peaks.json "
                          f"({sorted(peaks)})")
    return devices


# ------------------------------------------------------------- statistics
def nearest_rank(values, p: float) -> float:
    """The p-th percentile by nearest rank: the ceil(n*p/100)-th smallest."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    return xs[max(0, math.ceil(len(xs) * p / 100.0) - 1)]


# ------------------------------------------------------------------- run
@dataclasses.dataclass
class Kind:
    """One request kind of the mix, resolved against the configuration."""

    image: str
    filter: str
    spec: dict
    shape: tuple[int, int]
    dtype: np.dtype = np.dtype(np.uint8)   # the pixels' container

    @property
    def pixels(self) -> int:
        return self.shape[0] * self.shape[1]

    @property
    def bytes_per_px(self) -> int:
        return self.dtype.itemsize

    @property
    def ksize(self) -> tuple[int, int]:
        return kernel_size(self.spec)


@dataclasses.dataclass
class RunRecord:
    """What a metric reader sees of one run."""

    cell: Cell
    kinds: list[Kind]
    requests: list[loadgen.Request]
    t_start: float                 # window opens (clock)
    t_end: float                   # window closes: the last send allowed
    t_last: float                  # the last answer came
    setup_s: float
    stats_before: dict             # server stats() when the window opened
    stats: dict                    # ... and once every answer came
    events: list[dict] | None      # obs trace events (traced runs)
    device: devtrace.Reduction | None
    peak: dict                     # the chip's published peaks
    pools: dict                    # image name -> (count, H, W) pixels

    def latencies_ms(self) -> list[float]:
        """done - due of every request, failed ones at their failure."""
        return [(r.done - (self.t_start + r.due)) * 1e3
                for r in self.requests if not math.isnan(r.done)]

    def spans(self) -> dict[int, dict[str, float]]:
        """seq -> {stage: ts} of the requests submitted in the window."""
        out: dict[int, dict[str, float]] = {}
        for ev in self.events or ():
            seq = ev.get("seq")
            if seq is not None:
                out.setdefault(seq, {}).setdefault(ev["event"], ev["ts"])
        return {s: st for s, st in out.items()
                if st.get("submit", st.get("enqueue", -1.0)) >= self.t_start}


class Answers:
    """Every answer's shape and dtype (the kind's pixel container) is
    checked as it comes; a seeded reservoir keeps `k` answers for the
    comparison with the reference after the window."""

    def __init__(self, kinds: list[Kind], k: int, seed: int) -> None:
        self.kinds = kinds
        self.k = int(k)
        self.rng = random.Random(int(seed))
        self.seen = 0
        self.malformed = 0
        self.sample: list[tuple[loadgen.Request, np.ndarray]] = []
        self._lock = threading.Lock()

    def __call__(self, req: loadgen.Request, out) -> None:
        kind = self.kinds[req.kind]
        out = np.asarray(out)
        with self._lock:
            if out.shape != kind.shape or out.dtype != kind.dtype:
                self.malformed += 1
                return
            self.seen += 1
            if len(self.sample) < self.k:
                self.sample.append((req, out))
            else:
                j = self.rng.randrange(self.seen)
                if j < self.k:
                    self.sample[j] = (req, out)


class CompileCounter:
    """Counts JAX traces and uncached backend compiles while `active`."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")
    _registered: "CompileCounter | None" = None

    def __init__(self) -> None:
        self.active = False
        self.counts = {e.rsplit("/", 1)[1]: 0 for e in self.EVENTS}

    def _listen(self, event: str, duration: float, **_) -> None:
        if self.active and event in self.EVENTS:
            key = event.rsplit("/", 1)[1]
            self.counts[key] += 1

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._registered is None:
            import jax.monitoring
            counter = cls()
            jax.monitoring.register_event_duration_secs_listener(
                counter._listen)
            cls._registered = counter
        counter = cls._registered
        counter.counts = {k: 0 for k in counter.counts}
        return counter


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of `devices` (0 where unreported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def resolve_kinds(cell: Cell) -> list[Kind]:
    cfg = cell.config
    dtype = pixel_dtype(cfg["bit_depth"])
    kinds = []
    for m in cell.traffic["mix"]:
        if m["image"] not in cfg["images"] or m["filter"] not in cfg["filters"]:
            raise CellError(f"mix entry {m} names an image or filter that "
                            f"config {cfg['name']!r} does not state")
        kinds.append(Kind(m["image"], m["filter"], cfg["filters"][m["filter"]],
                          tuple(cfg["images"][m["image"]]), dtype))
    return kinds


def server_config(cell: Cell, trace: bool):
    from repro.serve import ServerConfig

    kw = dict(cell.config.get("server", {}))
    kw.update(cell.traffic.get("server", {}))
    if kw.get("pool") is not None:
        kw["pool"] = tuple(tuple(int(i) for i in m) for m in kw["pool"])
    return ServerConfig(**kw, trace=True if trace else None)


def multiplier(cell: Cell, control: bool = False) -> dict:
    """The multiplier the cell serves with: the configuration's method at
    the pixels' bit depth up to 8 bits, and at 16 bits, the width of
    their container, above; or with `control` the configuration's control
    (a path of the program that breaks the guarantee it states)."""
    cfg = cell.config
    if control:
        return dict(cfg["control"])
    bit_depth = int(cfg["bit_depth"])
    return {"method": cfg["method"],
            "nbits": bit_depth if bit_depth <= 8 else 16}


def drive(srv, cell: Cell, kinds: list[Kind], pools: dict, mult: dict,
          seed: int, seconds: float, answers: Answers, t0: float,
          annotate: bool) -> list[loadgen.Request]:
    """Offer the cell's traffic to `srv` for `seconds` from `t0`; returns
    every request, answered or failed."""
    traffic = cell.traffic
    pool = int(cell.config["distinct_images"])

    def submit(req: loadgen.Request):
        kind = kinds[req.kind]
        img = pools[kind.image][req.image]
        if annotate:
            import jax.profiler
            with jax.profiler.TraceAnnotation("chipbench.submit"):
                return srv.submit(img, kind.filter, **mult,
                                  timeout=loadgen.RESULT_TIMEOUT_S)
        return srv.submit(img, kind.filter, **mult,
                          timeout=loadgen.RESULT_TIMEOUT_S)

    if traffic["loop"] == "closed":
        tables = loadgen.closed_tables(traffic, pool, seed)
        return loadgen.run_closed(tables, int(traffic["in_flight"]), submit,
                                  answers, t0, t0 + seconds, clock=clock)
    reqs = loadgen.schedule(traffic, seconds, pool, seed)
    loadgen.run_scheduled(reqs, submit, answers, t0, clock=clock)
    return reqs


def prepare(cell: Cell, seed: int, mult: dict, *, trace: bool = False):
    """Set-up of a run: the cell's request kinds, its seeded image pools,
    and a server warmed on the cell's own shapes, filters and batch sizes,
    each kind once served through the whole path. Returns
    (kinds, pools, server); the caller closes the server."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro.serve import ImageFilterServer

    cfg = cell.config
    kinds = resolve_kinds(cell)
    names = sorted({k.image for k in kinds})
    pools = inputs.image_pools({n: cfg["images"][n] for n in names},
                               int(cfg["distinct_images"]), seed,
                               int(cfg["bit_depth"]))
    srv = ImageFilterServer(server_config(cell, trace))
    try:
        for shape, filt in sorted({(k.shape, k.filter) for k in kinds}):
            srv.warmup([shape], [filt], methods=[mult["method"]],
                       nbits=mult["nbits"],
                       batches=tuple(cfg["warm_batches"]))
        for kind in kinds:
            srv.submit(pools[kind.image][0], kind.filter,
                       **mult).result(600)
    except BaseException:
        srv.close()
        raise
    return kinds, pools, srv


def run_cell(cell: Cell, seed: int, seconds: float, *, trace: bool = False,
             t_process: float | None = None, devices=None,
             control: bool = False, peak: dict | None = None) -> dict:
    """One run of `cell`; returns the result line as a dict. `control`
    serves with the configuration's control in place of its multiplier;
    `devices` are the devices `check_device` returned (default: JAX's)."""
    import jax

    t_process = clock() if t_process is None else t_process
    devices = jax.devices() if devices is None else devices
    used = devices[:cell.chips]
    mult = multiplier(cell, control)
    kinds, pools, srv = prepare(cell, seed, mult, trace=trace)
    answers = Answers(kinds, int(cell.traffic.get("check_sample", 256)), seed)
    counter = CompileCounter.get()
    logdir = (tempfile.TemporaryDirectory(prefix="chipbench-trace-")
              if trace else None)
    try:
        stats_before = srv.stats()
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(logdir.name, profiler_options=opts)
        counter.active = True
        t0 = clock()
        with (jax.profiler.TraceAnnotation(WINDOW_EVENT) if trace
              else contextlib.nullcontext()):
            reqs = drive(srv, cell, kinds, pools, mult, seed, seconds,
                         answers, t0, annotate=trace)
        t_last = max((r.done for r in reqs if not math.isnan(r.done)),
                     default=t0)
        counter.active = False
        if trace:
            jax.profiler.stop_trace()
        stats = srv.stats()
        events = srv.trace.events() if trace else None
        mem = memory_peak(used)
    finally:
        counter.active = False
        srv.close()
    reduction = None
    if trace:
        try:
            traced = devtrace.load_xplane(devtrace.find_xplane(logdir.name))
            reduction = devtrace.reduce_trace(
                traced, devtrace.load_kernel_patterns(),
                window=devtrace.event_span(traced, WINDOW_EVENT))
        finally:
            logdir.cleanup()
    record = RunRecord(cell, kinds, reqs, t0, t0 + seconds, t_last,
                       t0 - t_process, stats_before, stats, events,
                       reduction, peak or {}, pools)
    checks = check_answers(record, answers)
    return result_line(record, checks, devices, used, mem, counter.counts,
                       trace)


def check_answers(record: RunRecord, answers: Answers) -> dict:
    """The numbers compared, each with its limit: every request answered,
    every answer well formed, and the sampled answers equal to the plain
    reference of the configuration's method and bit depth, pixel for
    pixel."""
    cfg = record.cell.config
    refs: dict[tuple[int, int], np.ndarray] = {}
    mismatched = 0
    for req, out in answers.sample:
        key = (req.kind, req.image)
        if key not in refs:
            kind = record.kinds[req.kind]
            refs[key] = filter_ref(record.pools[kind.image][req.image],
                                   kind.spec, method=cfg["method"],
                                   bit_depth=int(cfg["bit_depth"]))
        mismatched += int(np.count_nonzero(out != refs[key]))
    unanswered = sum(1 for r in record.requests if not r.ok)
    return {
        "unanswered": {"value": unanswered, "limit": 0},
        "malformed": {"value": answers.malformed, "limit": 0},
        "mismatched_px": {"value": mismatched, "limit": 0},
        "compared": {"value": len(answers.sample), "min": 1},
    }


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] if "limit" in c
               else c["value"] >= c["min"] for c in checks.values())


def lateness_ms(record: RunRecord) -> list[float]:
    """How late the generator sent each request after its due instant."""
    return [(r.sent - (record.t_start + r.due)) * 1e3
            for r in record.requests if not math.isnan(r.sent)]


def result_line(record: RunRecord, checks: dict, devices, used, mem: int,
                compiles: dict, trace: bool) -> dict:
    """The run's last line: metrics, device, breakdown, load, checks."""
    metrics = {}
    for m in record.cell.per_layer if trace else record.cell.end_to_end:
        value = load_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    line = {"correct": passed(checks), "attempted": len(record.requests),
            "failed": checks["unanswered"]["value"], "metrics": metrics,
            "device": device}
    red = record.device
    if red is not None:
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        line["breakdown"] = {"device_ops": red.device_ops,
                             "idle_gaps": red.idle_gaps}
    late = lateness_ms(record)
    line["load"] = {
        "loop": record.cell.traffic["loop"],
        "window_s": record.t_end - record.t_start,
        "drain_s": max(record.t_last - record.t_end, 0.0),
        "late_p95_ms": nearest_rank(late, 95) if late else None,
        "late_max_ms": max(late) if late else None,
        **{f"{k}_in_window": v for k, v in compiles.items()},
    }
    if red is not None:
        line["load"]["unmatched_op_share"] = red.unmatched_share
    line["checks"] = checks
    return line
