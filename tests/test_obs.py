"""Observability-layer tests (DESIGN.md §15): the metrics registry's
thread-safety/bounding/atomic-snapshot contract, per-request trace span
invariants on a real server (exactly one terminal per submitted request,
monotone stage timestamps), the worker's phase spans (they tile its time,
nest inside each dispatch, and cost nothing with tracing off), the
host -> device transfer counters, the Chrome trace-event export, the
one-lock `stats()` conservation identity, bit-identity with tracing on,
and a hypothesis property driving the batcher+recorder through random
schedules (spans are never lost or duplicated, whatever the interleave).

Like test_serve.py, the deterministic pieces run on a fake clock and
server tests force flushes via the size trigger / drain-on-close path.
"""
import collections
import json
import threading

import numpy as np
import pytest

from repro.filters import apply_filter
from repro.obs import (
    NOOP,
    PHASES,
    STAGES,
    TERMINALS,
    MetricsRegistry,
    TraceRecorder,
    chrome_trace,
    resolve_trace,
    span,
    trace_scope,
)
from repro.obs import trace as obs_trace
from repro.obs.snapshot import load_jsonl
from repro.obs.snapshot import main as snapshot_main
from repro.serve import (
    FilterFuture,
    FilterRequest,
    ImageFilterServer,
    ServerConfig,
    ShapeBucketedBatcher,
)
from repro.serve.request import DeadlineExceeded

RNG = np.random.default_rng(15)

#: far-future deadline so only size/drain triggers fire (deterministic)
FAR = 3600_000.0

#: the stats() keys the operator surface promises (schema stability --
#: the §15 smoke guard reads the same list via serve_bench)
STATS_KEYS = {
    "submitted", "served", "failed", "shed", "shed_overload",
    "fast_failed", "errors", "last_error", "batches", "occupancy",
    "flush_reasons", "served_priority", "pending", "pressure",
    "rejected", "tenants", "compile", "plan_memo", "healthy", "state",
    "degraded", "isolated", "retries", "dispatch_failures",
    "conv_tap_leaves",
}


def image(seed: int, shape=(24, 20)) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 256, shape).astype(np.int32)


def make_req(seq: int, *, t: float = 0.0, shape=(24, 20),
             filt="gaussian3", priority="normal") -> FilterRequest:
    return FilterRequest(img=image(seq, shape), filt=filt, method="refmlm",
                         exec="local", nbits=8,
                         future=FilterFuture(), submitted=t, seq=seq,
                         priority=priority)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# -------------------------------------------------------- metrics registry

class TestMetricsRegistry:
    def test_counter_gauge_histogram_basics(self):
        m = MetricsRegistry()
        c = m.counter("c")
        c.inc()
        c.inc(2, tenant="a")
        assert c.value() == 1 and c.value(tenant="a") == 2
        assert c.total() == 3
        assert c.group_by("tenant") == {"a": 2}
        g = m.gauge("g")
        g.set(5)
        g.add(-2)
        assert g.value() == 3
        h = m.histogram("h", buckets=(1.0, 10.0))
        h.observe(0.5)
        h.observe(5.0)
        h.observe(50.0)
        s = h.series()
        assert s["count"] == 3 and s["sum"] == 55.5
        # per-bin counts: <=1, (1, 10], >10
        assert s["buckets"] == {"le_1": 1, "le_10": 1, "le_inf": 1}

    def test_get_or_create_returns_same_handle(self):
        m = MetricsRegistry()
        assert m.counter("x") is m.counter("x")
        with pytest.raises(TypeError):
            m.gauge("x")        # name already bound to a counter

    def test_series_bounding_drops_not_raises(self):
        m = MetricsRegistry(max_series=4)
        c = m.counter("c")
        for i in range(10):
            c.inc(tenant=f"t{i}")
        snap = m.snapshot()
        assert snap["series"] <= 4
        assert snap["dropped_series"] == 6
        assert c.total() == 4          # dropped observations vanish, cleanly

    def test_snapshot_is_atomic_under_concurrent_writers(self):
        m = MetricsRegistry()
        a, b = m.counter("a"), m.counter("b")
        stop = threading.Event()

        def writer():
            while not stop.is_set():
                # a and b move together under one hold(): every snapshot
                # must observe a == b
                with m.hold():
                    a.inc()
                    b.inc()

        threads = [threading.Thread(target=writer) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            for _ in range(200):
                with m.hold():
                    assert a.value() == b.value()
        finally:
            stop.set()
            for t in threads:
                t.join()

    def test_concurrent_increments_lose_nothing(self):
        m = MetricsRegistry()
        c = m.counter("c")
        n, per = 8, 500

        def worker():
            for _ in range(per):
                c.inc()

        threads = [threading.Thread(target=worker) for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value() == n * per


# ------------------------------------------------------------ the recorder

class TestTraceRecorder:
    def test_noop_is_off_and_free(self):
        assert not NOOP.enabled
        NOOP.event("submit", seq=1)        # must not raise, must not record
        assert resolve_trace(None, clock=FakeClock()) is NOOP
        assert resolve_trace(False, clock=FakeClock()) is NOOP

    def test_events_bounded(self):
        rec = TraceRecorder(clock=FakeClock(), max_events=10)
        for i in range(25):
            rec.event("submit", seq=i)
        assert len(rec.events()) == 10
        assert rec.summary()["dropped"] == 15

    def test_spans_sorted_and_keyed_by_seq(self):
        clk = FakeClock()
        rec = TraceRecorder(clock=clk)
        rec.event("enqueue", ts=1.0, seq=7, bucket="b")
        rec.event("submit", ts=0.5, seq=7, bucket="b")
        rec.event("fault", site="s")       # no seq: aux, not a span
        spans = rec.spans()
        assert list(spans) == [7]
        assert [e["event"] for e in spans[7]] == ["submit", "enqueue"]

    def test_jsonl_roundtrip(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        rec = TraceRecorder(path, clock=FakeClock())
        rec.event("submit", ts=0.0, seq=1, bucket="b")
        rec.event("fulfil", ts=1.0, seq=1, bucket="b")
        rec.close()
        back = load_jsonl(path)
        assert [e["event"] for e in back] == ["submit", "fulfil"]
        assert TraceRecorder.from_events(back).summary()["spans"] == 1

    def test_chrome_trace_shape(self):
        rec = TraceRecorder(clock=FakeClock())
        rec.event("enqueue", ts=0.0, seq=1, bucket="b")
        rec.event("flush", ts=1.0, seq=1, bucket="b")
        rec.event("dispatch", ts=1.0, seq=1, bucket="b")
        rec.event("fulfil", ts=2.0, seq=1, bucket="b")
        doc = chrome_trace(rec.events())
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        kinds = collections.Counter(e["ph"] for e in doc["traceEvents"])
        assert kinds["X"] == 2          # queued + dispatch slices
        assert kinds["M"] >= 1          # track naming metadata
        for e in doc["traceEvents"]:
            if e["ph"] == "X":
                assert e["dur"] >= 0


    def test_span_inherits_parent_fields_through_the_scope_stack(self):
        clk = FakeClock()
        rec = TraceRecorder(clock=clk)
        with trace_scope(rec):
            with rec.span("dispatch", batch=7, member="m0") as fields:
                assert fields["batch"] == 7
                clk.t = 1.0
                with span("prep"):          # the module-level form
                    clk.t = 3.0
        prep, dispatch = rec.events("span")
        assert (prep["span"], prep["parent"], prep["batch"],
                prep["member"]) == ("prep", "dispatch", 7, "m0")
        assert (prep["ts"], prep["end"]) == (1.0, 3.0)
        assert (dispatch["ts"], dispatch["end"], dispatch["parent"]) == (
            0.0, 3.0, None)
        assert rec.summary()["phase_s"]["prep"]["max"] == 2.0
        with span("prep"):                  # nothing active: not recorded
            pass
        assert len(rec.events("span")) == 2

    def test_chrome_trace_puts_phases_on_worker_and_member_tracks(self):
        rec = TraceRecorder(clock=FakeClock())
        for name, t0, t1, member in (("idle", 0.0, 1.0, None),
                                     ("batch", 1.0, 4.0, None),
                                     ("prep", 1.0, 2.0, "m0"),
                                     ("fetch", 2.0, 3.0, "m1")):
            fields = {} if member is None else {"member": member}
            rec.event("span", ts=t0, span=name, end=t1, parent=None,
                      thread="w", **fields)
        doc = chrome_trace(rec.events())
        slices = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        assert set(slices) == {"idle", "batch", "prep", "fetch"}
        assert slices["prep"]["dur"] == 1e6 and slices["prep"]["cat"] == "phase"
        assert slices["batch"]["cat"] == "span"
        assert slices["idle"]["tid"] == slices["batch"]["tid"]
        tracks = {slices[n]["tid"] for n in ("idle", "prep", "fetch")}
        assert len(tracks) == 3
        names = {e["args"]["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M"}
        assert {"w", "w member m0", "w member m1"} <= names


# ------------------------------------------- spans on a real server

def serve_all(srv, futs, timeout=60):
    out = []
    for f in futs:
        try:
            out.append(f.result(timeout))
        except Exception as err:  # noqa: BLE001 -- outcome, not failure
            out.append(err)
    return out


class TestServerTracing:
    def test_exactly_one_terminal_per_request_mixed_priorities(self):
        cfg = ServerConfig(max_batch=4, max_delay_ms=FAR, trace=True)
        srv = ImageFilterServer(cfg)
        futs = [srv.submit(image(i), "gaussian3",
                           priority=("high", "normal", "low")[i % 3],
                           tenant=f"t{i % 2}")
                for i in range(20)]
        srv.close()            # drain flushes the sub-max_batch remainders
        serve_all(srv, futs)
        spans = srv.trace.spans()
        stats = srv.stats()
        assert len(spans) == stats["submitted"] == 20
        for seq, evs in spans.items():
            names = [e["event"] for e in evs]
            assert sum(n in TERMINALS for n in names) == 1, (seq, names)
            # stage order is monotone in both time and pipeline position
            ts = [e["ts"] for e in evs]
            assert ts == sorted(ts), (seq, names, ts)
            order = [STAGES.index(n) for n in names if n in STAGES]
            assert order == sorted(order), (seq, names)

    def test_shed_requests_get_shed_terminal(self):
        cfg = ServerConfig(max_batch=64, max_delay_ms=FAR, trace=True)
        srv = ImageFilterServer(cfg, clock=FakeClock())
        fut = srv.submit(image(0), "box3", deadline_ms=0.0)
        srv._clock.t = 10.0
        srv.close()                     # drain sweeps the expired request
        assert isinstance(fut.exception(), DeadlineExceeded)
        spans = srv.trace.spans()
        assert len(spans) == 1
        (evs,) = spans.values()
        assert [e["event"] for e in evs][-1] == "shed"
        assert evs[-1]["cause"] == "deadline"

    def test_rejects_are_aux_events_not_spans(self):
        cfg = ServerConfig(max_batch=64, max_delay_ms=FAR, max_pending=1,
                           admission_timeout_s=0.01, trace=True)
        srv = ImageFilterServer(cfg, clock=FakeClock())
        srv.submit(image(0), "box3")
        with pytest.raises(Exception):
            srv.submit(image(1), "box3", timeout=0.0)
        srv.close()
        rejects = srv.trace.events("reject")
        assert len(rejects) == 1 and "seq" not in rejects[0]
        assert srv.trace.summary()["spans"] == 1

    def test_bit_identity_with_tracing_on(self):
        img = image(3, (32, 24))
        with ImageFilterServer(ServerConfig(max_batch=2, max_delay_ms=FAR,
                                            trace=True)) as srv:
            futs = [srv.submit(img, "sobel_x"), srv.submit(img, "sobel_x")]
            outs = [np.asarray(f.result(60)) for f in futs]
        ref = np.asarray(apply_filter(img, "sobel_x"))
        assert np.array_equal(outs[0], ref)
        assert np.array_equal(outs[1], ref)

    def test_trace_off_is_noop_and_absent(self):
        srv = ImageFilterServer(ServerConfig(max_batch=2, max_delay_ms=FAR))
        fut = srv.submit(image(0), "box3")
        srv.close()            # drain serves the lone sub-max_batch request
        fut.result(60)
        assert srv.trace is NOOP

    def test_leaf_spans_tile_the_worker_and_nest_in_each_dispatch(self):
        # frames large enough that a batch's work dwarfs the executor's
        # fixed bookkeeping (plan memo, fault probe), as at serving sizes
        shape = (480, 640)
        cfg = ServerConfig(max_batch=4, max_delay_ms=1.0, trace=True)
        with ImageFilterServer(cfg) as srv:
            srv.warmup([shape], ["gaussian3"], batches=(1, 2, 4))
            imgs = [image(i, shape) for i in range(8)]
            for wave in (imgs[:4], imgs[4:]):
                serve_all(srv, [srv.submit(im, "gaussian3") for im in wave])
        evs = srv.trace.events("span")
        worker = [e for e in evs if e["thread"] == "repro-serve-worker"]
        leaves = sorted(((e["ts"], e["end"]) for e in worker
                         if e["span"] in PHASES))
        assert {e["span"] for e in worker} == set(PHASES) | {"batch",
                                                              "dispatch"}
        covered, reach = 0.0, leaves[0][0]
        for t0, t1 in leaves:
            covered += max(t1 - max(t0, reach), 0.0)
            reach = max(reach, t1)
        assert covered >= 0.99 * (reach - leaves[0][0])
        dispatches = [e for e in worker if e["span"] == "dispatch"]
        assert len(dispatches) == srv.stats()["batches"]
        inner = ("prep", "upload", "launch", "device_wait", "fetch")
        for d in dispatches:
            kids = [e for e in worker if e.get("batch") == d["batch"]
                    and e["span"] in inner]
            assert sorted(e["span"] for e in kids) == sorted(inner)
            for e in kids:
                assert e["parent"] == "dispatch"
                assert d["ts"] <= e["ts"] <= e["end"] <= d["end"]
            assert (sum(e["end"] - e["ts"] for e in kids)
                    >= 0.95 * (d["end"] - d["ts"]))
        # a request's dispatch event names the batch that served it
        batches = {d["batch"] for d in dispatches}
        assert {e["batch"] for e in srv.trace.events("dispatch")} == batches

    def test_trace_off_builds_no_span_or_annotation(self, monkeypatch):
        built = collections.Counter()

        class Counting:
            def __init__(self, kind, real):
                self.kind, self.real = kind, real

            def __call__(self, *a, **k):
                built[self.kind] += 1
                return self.real(*a, **k)

        monkeypatch.setattr(obs_trace, "_Span",
                            Counting("span", obs_trace._Span))
        monkeypatch.setattr(obs_trace, "TraceAnnotation",
                            Counting("annotation", obs_trace.TraceAnnotation))
        monkeypatch.setattr(NOOP.__class__, "event",
                            lambda self, *a, **k: built.update(["event"]))
        for trace in (None, True):
            built.clear()
            with ImageFilterServer(ServerConfig(max_batch=2,
                                                max_delay_ms=FAR,
                                                trace=trace)) as srv:
                serve_all(srv, [srv.submit(image(i), "box3")
                                for i in range(2)])
            if trace is None:
                assert not built, built
            else:
                assert built["span"] and built["annotation"]

    def test_transfer_counters_price_pad_slots_and_real_pixels(self):
        h, w = 24, 20
        with ImageFilterServer(ServerConfig(max_batch=4,
                                            max_delay_ms=FAR)) as srv:
            # a size flush of 4 and a drained batch of 3, traced as 4
            futs = [srv.submit(image(i, (h, w)), "box3") for i in range(7)]
            srv.close()
            serve_all(srv, futs)
            transfer = srv.stats()["transfer"]
        assert transfer == {"h2d_bytes": {"": 4 * h * w * 8},
                            "pixels": {"": h * w * 7},
                            "d2h_bytes": {"uint8": 1 * h * w * 8}}

    def test_snapshot_cli(self, tmp_path, capsys):
        path = str(tmp_path / "t.jsonl")
        with ImageFilterServer(ServerConfig(max_batch=2, max_delay_ms=FAR,
                                            trace=path)) as srv:
            futs = [srv.submit(image(i), "box3") for i in range(4)]
            serve_all(srv, futs)
        chrome = str(tmp_path / "t.chrome.json")
        assert snapshot_main([path, "--chrome", chrome]) == 0
        out = capsys.readouterr().out
        assert "spans: 4" in out and "WARNING" not in out
        assert "phases (ms):" in out and "device_wait" in out
        doc = json.load(open(chrome))
        assert doc["traceEvents"]
        assert any(e.get("cat") == "phase" for e in doc["traceEvents"])


# ------------------------------------------------- consistent stats()

class TestConsistentStats:
    def test_conservation_identity_under_load(self):
        """`served + failed + shed <= submitted` in EVERY snapshot -- the
        §15 one-lock fix; previously a flush between reads could show
        more outcomes than admissions."""
        cfg = ServerConfig(max_batch=4, max_delay_ms=0.5)
        violations = []
        stop = threading.Event()
        with ImageFilterServer(cfg) as srv:

            def prober():
                while not stop.is_set():
                    s = srv.stats()
                    outcomes = (s["served"] + s["failed"] + s["shed"]
                                + s["shed_overload"])
                    if outcomes > s["submitted"]:
                        violations.append(s)

            t = threading.Thread(target=prober)
            t.start()
            try:
                futs = [srv.submit(image(i % 7, (16, 12)), "box3")
                        for i in range(60)]
                serve_all(srv, futs)
            finally:
                stop.set()
                t.join()
            final = srv.stats()
        assert not violations
        assert final["served"] == final["submitted"] == 60

    def test_stats_schema_keys_stable(self):
        srv = ImageFilterServer(ServerConfig(max_batch=2, max_delay_ms=FAR))
        fut = srv.submit(image(0), "box3")
        srv.close()            # drain serves the lone sub-max_batch request
        fut.result(60)
        assert STATS_KEYS <= set(srv.stats())


# ------------------------------------ property: random schedules

def test_random_schedules_never_lose_or_duplicate_spans():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    step = st.tuples(st.integers(0, 2),     # 0=submit 1=advance 2=flush
                     st.integers(0, 2),     # shape choice on submit
                     st.integers(0, 2))     # priority choice on submit

    @settings(max_examples=40, deadline=None)
    @given(st.lists(step, min_size=1, max_size=40))
    def run(steps):
        clk = FakeClock()
        rec = TraceRecorder(clock=clk)
        b = ShapeBucketedBatcher(max_batch=3, max_delay_s=5.0, clock=clk,
                                 trace=rec)
        seq = 0
        flushed = []
        for op, shp, pri in steps:
            if op == 0:
                seq += 1
                b.add(make_req(seq, t=clk.t,
                               shape=[(8, 8), (8, 10), (12, 8)][shp],
                               priority=["high", "normal", "low"][pri]))
                rec.event("submit", ts=clk.t, seq=seq)
            elif op == 1:
                clk.t += 2.0
            else:
                flushed += b.ready(clk.t)
        flushed += b.drain()
        # every submitted request appears in exactly one flushed batch,
        # and its span carries exactly one enqueue (and, iff flushed by
        # now, exactly one flush) -- no loss, no duplication
        served = [r.seq for f in flushed for r in f.requests]
        assert sorted(served) == sorted(set(served))
        spans = rec.spans()
        assert set(spans) == set(range(1, seq + 1))
        for s, evs in spans.items():
            names = [e["event"] for e in evs]
            assert names.count("enqueue") == 1
            assert names.count("flush") == (1 if s in served else 0)
            ts = [e["ts"] for e in evs]
            assert ts == sorted(ts)

    run()
