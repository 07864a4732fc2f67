"""Serving-layer tests (DESIGN.md §10): batcher flush-policy invariants,
exactly-once delivery under concurrent mixed-shape/mixed-filter load,
bit-identity of served vs direct single-image outputs for every filter ×
multiplier × exec mode, admission backpressure, and the warm-start
compile-cache / per-bucket plan memoisation.

The batcher is a pure state machine driven with a fake clock (no sleeps,
no flaky timing); server tests force deterministic flushes via the size
trigger (max_delay set far out) or the drain-on-close path.
"""
import threading

import numpy as np
import pytest

from repro.filters import (
    FILTER_NAMES,
    apply_filter,
    apply_filter_batch,
    resolve_filter_blocks,
)
from repro.serve import (
    BatchExecutor,
    FilterFuture,
    FilterRequest,
    ImageFilterServer,
    ServerClosed,
    ServerConfig,
    ServerOverloaded,
    ShapeBucketedBatcher,
    bucket_key,
    next_pow2,
    serve_key,
)
from repro.serve import batcher as batcher_mod
from repro.tuning import resolve_blocks, resolve_blocks_cached
from repro.tuning.blocks import BlockConfig

RNG = np.random.default_rng(7)

#: far-future deadline so only size/drain triggers fire (deterministic)
FAR = 3600_000.0


def image(seed: int, shape=(24, 20)) -> np.ndarray:
    """Deterministic per-seed image -- unique payloads make any dropped,
    duplicated, or cross-wired response detectable by value."""
    return np.random.default_rng(seed).integers(
        0, 256, shape).astype(np.int32)


def make_req(seq: int, *, t: float = 0.0, shape=(24, 20),
             filt="gaussian3", method="refmlm",
             exec_mode="local") -> FilterRequest:
    return FilterRequest(img=image(seq, shape), filt=filt, method=method,
                         exec=exec_mode, nbits=8,
                         future=FilterFuture(), submitted=t, seq=seq)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# --------------------------------------------------------------- the batcher

class TestBatcherPolicy:
    def test_size_trigger_pops_exactly_max_batch(self):
        clk = FakeClock()
        b = ShapeBucketedBatcher(max_batch=4, max_delay_s=10.0, clock=clk)
        for i in range(9):
            b.add(make_req(i))
        flushed = b.ready()
        assert [f.reason for f in flushed] == ["size", "size"]
        assert [len(f.requests) for f in flushed] == [4, 4]
        assert b.pending == 1          # remainder keeps its arrival time
        assert b.ready() == []         # no trigger fires for the remainder

    def test_deadline_trigger_flushes_partial(self):
        clk = FakeClock()
        b = ShapeBucketedBatcher(max_batch=8, max_delay_s=0.005, clock=clk)
        b.add(make_req(0, t=0.0))
        b.add(make_req(1, t=0.004))
        assert b.ready(now=0.004) == []
        assert b.next_deadline() == pytest.approx(0.005)
        flushed = b.ready(now=0.006)
        assert len(flushed) == 1 and flushed[0].reason == "deadline"
        assert len(flushed[0].requests) == 2
        assert b.pending == 0 and b.next_deadline() is None

    def test_buckets_never_mix(self):
        b = ShapeBucketedBatcher(max_batch=2, max_delay_s=10.0,
                                 clock=FakeClock())
        reqs = [make_req(0, shape=(16, 16)),
                make_req(1, shape=(24, 20)),
                make_req(2, shape=(16, 16), filt="sobel_x"),
                make_req(3, shape=(16, 16)),
                make_req(4, shape=(16, 16), method="exact")]
        for r in reqs:
            b.add(r)
        flushed = b.ready()            # only the (16,16) gaussian3 pair fires
        assert len(flushed) == 1
        assert {r.seq for r in flushed[0].requests} == {0, 3}
        for batch in flushed + b.drain():
            keys = {r.key for r in batch.requests}
            assert keys == {batch.key}      # every batch is one bucket

    def test_fifo_within_bucket_and_exactly_once(self):
        b = ShapeBucketedBatcher(max_batch=3, max_delay_s=10.0,
                                 clock=FakeClock())
        for i in range(8):
            b.add(make_req(i))
        seen = []
        for batch in b.ready() + b.drain():
            seen.extend(r.seq for r in batch.requests)
        assert seen == list(range(8))       # FIFO, no drop/dup/reorder
        assert b.pending == 0

    def test_validation(self):
        with pytest.raises(ValueError):
            ShapeBucketedBatcher(max_batch=0, max_delay_s=1.0)
        with pytest.raises(ValueError):
            ShapeBucketedBatcher(max_batch=1, max_delay_s=-1.0)

    def test_sparse_bucket_flushes_at_once(self):
        """A lone request whose bucket last saw a request at least the
        flush delay earlier is cut at once, not on the deadline."""
        b = ShapeBucketedBatcher(max_batch=8, max_delay_s=0.002,
                                 clock=FakeClock())
        b.add(make_req(0, t=0.0))
        assert [f.reason for f in b.ready(now=0.002)] == ["deadline"]
        b.add(make_req(1, t=0.010))
        flushed = b.ready(now=0.010)
        assert [f.reason for f in flushed] == ["sparse"]
        assert [r.seq for r in flushed[0].requests] == [1]
        assert b.pending == 0

    def test_short_gap_holds_until_deadline(self):
        b = ShapeBucketedBatcher(max_batch=8, max_delay_s=0.002,
                                 clock=FakeClock())
        b.add(make_req(0, t=0.0))
        b.add(make_req(1, t=0.001))
        assert b.ready(now=0.001) == []
        assert [f.reason for f in b.ready(now=0.002)] == ["deadline"]
        b.add(make_req(2, t=0.0025))   # 1.5 ms after seq 1: holds
        assert b.ready(now=0.0025) == []
        flushed = b.ready(now=0.005)
        assert [f.reason for f in flushed] == ["deadline"]
        assert [r.seq for r in flushed[0].requests] == [2]

    def test_first_request_of_a_bucket_holds(self):
        b = ShapeBucketedBatcher(max_batch=8, max_delay_s=0.002,
                                 clock=FakeClock())
        b.add(make_req(0, t=5.0))
        assert b.ready(now=5.0) == []
        assert b.next_deadline() == pytest.approx(5.002)

    @pytest.mark.parametrize("other", ["size", "deadline"])
    def test_sparse_bucket_waits_while_other_work_is_ready(self, other):
        """Sparse flushes only fill a look that finds nothing else: a
        size or deadline batch in another bucket runs first."""
        b = ShapeBucketedBatcher(max_batch=2, max_delay_s=0.002,
                                 clock=FakeClock())
        b.add(make_req(0, t=0.0, shape=(16, 16)))
        assert [f.reason for f in b.ready(now=0.002)] == ["deadline"]
        if other == "size":
            b.add(make_req(1, t=0.009))
            b.add(make_req(2, t=0.009))
        else:
            b.add(make_req(1, t=0.0075))
        b.add(make_req(3, t=0.010, shape=(16, 16)))
        flushed = b.ready(now=0.010)
        assert [f.reason for f in flushed] == [other]
        assert {r.seq for r in flushed[0].requests} <= {1, 2}
        flushed = b.ready(now=0.010)
        assert [(f.reason, [r.seq for r in f.requests])
                for f in flushed] == [("sparse", [3])]

    def test_far_delay_never_sparse_flushes(self):
        b = ShapeBucketedBatcher(max_batch=8, max_delay_s=FAR / 1e3,
                                 clock=FakeClock())
        for i in range(4):
            b.add(make_req(i, t=1000.0 * i))
            assert b.ready(now=1000.0 * i) == []
        assert [len(f.requests) for f in b.drain()] == [4]

    def test_arrival_record_is_bounded_and_evicted_key_holds(self,
                                                            monkeypatch):
        monkeypatch.setattr(batcher_mod, "ARRIVALS_MAX", 3)
        b = ShapeBucketedBatcher(max_batch=8, max_delay_s=0.002,
                                 clock=FakeClock())
        shapes = [(8 + i, 8) for i in range(5)]
        for i, shape in enumerate(shapes):
            b.add(make_req(i, t=0.0, shape=shape))
            assert len(b._arrivals) <= 3
        assert sum(len(f.requests) for f in b.ready(now=0.002)) == 5
        # shapes[0] and [1] were evicted: they hold like a first request,
        # while the recorded shapes[4] is cut as sparse
        b.add(make_req(10, t=1.0, shape=shapes[0]))
        b.add(make_req(14, t=1.0, shape=shapes[4]))
        flushed = b.ready(now=1.0)
        assert [(f.reason, [r.seq for r in f.requests])
                for f in flushed] == [("sparse", [14])]
        assert b.pending == 1 and len(b._arrivals) == 3


def check_schedule(events, max_batch: int) -> list[str]:
    """Drive one add/advance/flush schedule; assert it partitions the
    requests -- exactly-once, FIFO per bucket, uniform bucket key per
    batch. Returns the flush reasons in order."""
    shapes = [(8, 8), (16, 12), (24, 20)]
    filters = ["gaussian3", "sobel_x", "box3"]
    clk = FakeClock()
    b = ShapeBucketedBatcher(max_batch=max_batch, max_delay_s=0.01,
                             clock=clk)
    keys, popped, reasons = {}, [], []
    for i, (si, fi, tick) in enumerate(events):
        clk.t += (0.0, 0.004, 0.012)[tick]
        keys[i] = b.add(make_req(i, t=clk.t, shape=shapes[si],
                                 filt=filters[fi]))
        for batch in b.ready():
            assert {r.key for r in batch.requests} == {batch.key}
            popped.extend(r.seq for r in batch.requests)
            reasons.append(batch.reason)
    for batch in b.drain():
        assert {r.key for r in batch.requests} == {batch.key}
        popped.extend(r.seq for r in batch.requests)
    assert sorted(popped) == list(keys)  # exactly once, none left
    for key in set(keys.values()):
        seqs = [s for s in popped if keys[s] == key]
        assert seqs == sorted(seqs)      # FIFO within a bucket
    assert b.pending == 0
    return reasons


@pytest.mark.parametrize("seed", range(4))
def test_batcher_seeded_schedule_exactly_once(seed):
    """Seeded schedules of adds and clock steps short and long against
    the flush delay: the size, deadline and sparse triggers all fire, and
    the requests are still partitioned."""
    rng = np.random.default_rng(seed)
    events = [tuple(int(v) for v in rng.integers(0, 3, 3))
              for _ in range(40)]
    assert set(check_schedule(events, 2)) == {"size", "deadline", "sparse"}


def test_batcher_random_schedule_exactly_once():
    """Property: any add/flush interleaving, clock steps short and long
    against the flush delay (so sparse flushes too), partitions the
    requests -- exactly-once, FIFO per bucket, uniform bucket key per
    batch."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 2), st.integers(0, 2),
                              st.integers(0, 2)), max_size=40),
           st.integers(1, 5))
    def run(events, max_batch):
        check_schedule(events, max_batch)

    run()


# ------------------------------------------------- served output bit-identity

#: the multiplier axis: exact, the paper's REFMLM, and Mitchell's
#: approximate multiplier (the `fvc2004_mitchell` cell serves it).
MULT_POINTS = ["exact", "refmlm", "mitchell"]


def serve_all(reqs, config) -> list[np.ndarray]:
    """Submit (img, filt, kwargs) tuples, drain, return outputs in order."""
    with ImageFilterServer(config) as srv:
        futs = [srv.submit(im, f, **kw) for im, f, kw in reqs]
        srv.close(drain=True)
    return [f.result(120) for f in futs]


class TestServedBitIdentity:
    @pytest.mark.parametrize("method", MULT_POINTS)
    @pytest.mark.parametrize("name", FILTER_NAMES)
    def test_local_every_filter_and_multiplier(self, name, method):
        """A coalesced batch serves each request bit-identically to the
        direct single-image apply_filter call."""
        imgs = [image(40 + i) for i in range(3)]
        kw = dict(method=method)
        outs = serve_all([(im, name, kw) for im in imgs],
                         ServerConfig(max_batch=4, max_delay_ms=FAR))
        for im, out in zip(imgs, outs):
            want = np.asarray(apply_filter(im, name, **kw))
            np.testing.assert_array_equal(out, want)

    @pytest.mark.parametrize("exec_mode", ["local", "sharded", "streamed"])
    @pytest.mark.parametrize("name", FILTER_NAMES)
    def test_every_filter_and_exec_mode(self, name, exec_mode):
        """Exec routing (§9) through the queue stays bit-identical to the
        direct local call, for every bank filter."""
        imgs = [image(60 + i) for i in range(2)]
        cfg = ServerConfig(max_batch=4, max_delay_ms=FAR, exec=exec_mode,
                           tile=(16, 16))
        outs = serve_all([(im, name, {}) for im in imgs], cfg)
        for im, out in zip(imgs, outs):
            want = np.asarray(apply_filter(im, name))
            np.testing.assert_array_equal(out, want)

    def test_output_independent_of_coalesced_batch(self):
        """The same request returns the same bytes whether it is served
        alone, amid strangers, or zero-padded to a pow-2 batch."""
        target = image(99)
        want = np.asarray(apply_filter(target, "gaussian5"))
        alone = serve_all([(target, "gaussian5", {})],
                          ServerConfig(max_batch=8, max_delay_ms=FAR))
        np.testing.assert_array_equal(alone[0], want)
        crowd = [(image(200 + i), "gaussian5", {}) for i in range(2)]
        mixed = serve_all(crowd + [(target, "gaussian5", {})] + crowd,
                          ServerConfig(max_batch=5, max_delay_ms=FAR))
        np.testing.assert_array_equal(mixed[2], want)


class TestExactlyOnceConcurrent:
    def test_concurrent_mixed_load(self):
        """Threads racing submissions of mixed shapes/filters: every request
        is answered exactly once with exactly its own output."""
        shapes = [(16, 16), (24, 20)]
        filters = ["gaussian3", "sobel_x"]
        per_thread, n_threads = 10, 4
        cfg = ServerConfig(max_batch=4, max_delay_ms=5.0, max_pending=128)
        results: dict[int, np.ndarray] = {}
        errs = []

        def client(tid: int, srv: ImageFilterServer):
            try:
                futs = []
                for j in range(per_thread):
                    uid = tid * per_thread + j
                    im = image(uid, shapes[uid % 2])
                    futs.append((uid, im,
                                 srv.submit(im, filters[(uid // 2) % 2])))
                for uid, im, fut in futs:
                    results[uid] = (im, fut.result(120))
            except Exception as e:              # noqa: BLE001
                errs.append(e)

        with ImageFilterServer(cfg) as srv:
            threads = [threading.Thread(target=client, args=(t, srv))
                       for t in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            stats = srv.stats()
        assert not errs
        total = per_thread * n_threads
        assert len(results) == total
        for uid, (im, out) in results.items():
            want = np.asarray(apply_filter(im, filters[(uid // 2) % 2]))
            np.testing.assert_array_equal(out, want)
        assert stats["submitted"] == stats["served"] == total
        assert stats["failed"] == 0 and stats["pending"] == 0
        # the occupancy histogram accounts for every request exactly once
        assert sum(n * c for n, c in stats["occupancy"].items()) == total
        assert sum(stats["flush_reasons"].values()) == stats["batches"]


def test_server_counts_sparse_flushes():
    """With a finite hold, a bucket's first request waits out the
    deadline; the next, submitted after that hold has passed, is cut at
    once as sparse, and `stats()` counts it by reason."""
    with ImageFilterServer(ServerConfig(max_batch=4,
                                        max_delay_ms=200.0)) as srv:
        outs = [srv.submit(image(i), "gaussian3").result(120)
                for i in range(2)]
        stats = srv.stats()
    for i, out in enumerate(outs):
        np.testing.assert_array_equal(
            out, np.asarray(apply_filter(image(i), "gaussian3")))
    assert stats["flush_reasons"] == {"deadline": 1, "sparse": 1}
    assert stats["occupancy"] == {1: 2}


# ------------------------------------------------- admission + lifecycle

class TestAdmission:
    def test_backpressure_rejects_when_full(self):
        cfg = ServerConfig(max_batch=64, max_delay_ms=FAR, max_pending=2,
                           admission_timeout_s=0.05)
        srv = ImageFilterServer(cfg)
        try:
            f1 = srv.submit(image(1), "gaussian3")
            f2 = srv.submit(image(2), "gaussian3")
            with pytest.raises(ServerOverloaded):
                srv.submit(image(3), "gaussian3")
            assert srv.stats()["rejected"] == 1
        finally:
            srv.close(drain=True)
        # the queued pair still completes correctly on drain
        np.testing.assert_array_equal(
            f1.result(1), np.asarray(apply_filter(image(1), "gaussian3")))
        assert f2.done()

    def test_close_undrained_fails_pending(self):
        srv = ImageFilterServer(ServerConfig(max_batch=64, max_delay_ms=FAR))
        fut = srv.submit(image(4), "gaussian3")
        srv.close(drain=False)
        with pytest.raises(ServerClosed):
            fut.result(5)
        with pytest.raises(ServerClosed):
            srv.submit(image(5), "gaussian3")

    def test_submit_validates_before_admission(self):
        srv = ImageFilterServer(ServerConfig())
        try:
            with pytest.raises(ValueError):
                srv.submit(image(6), "no_such_filter")
            with pytest.raises(ValueError):
                srv.submit(image(6), "gaussian3", exec="warp")
            with pytest.raises(ValueError):
                srv.submit(image(6), "gaussian3", priority="urgent")
            with pytest.raises(ValueError):
                srv.submit(np.zeros((2, 8, 8), np.int32), "gaussian3")
            assert srv.stats()["submitted"] == 0
        finally:
            srv.close()


# ---------------------------------------- warm cache + plan memoisation

class TestWarmupAndPlans:
    def test_next_pow2(self):
        assert [next_pow2(n) for n in (1, 2, 3, 4, 5, 8, 9)] == \
            [1, 2, 4, 4, 8, 8, 16]

    def test_warmup_amortises_first_request(self):
        cfg = ServerConfig(max_batch=4, max_delay_ms=FAR)
        with ImageFilterServer(cfg) as srv:
            keys = srv.warmup([(24, 20)], ["gaussian3"], batches=[1, 4])
            assert keys == [
                serve_key(bucket_key("gaussian3", "refmlm", "local",
                                     8, 24, 20), 1),
                serve_key(bucket_key("gaussian3", "refmlm", "local",
                                     8, 24, 20), 4)]
            futs = [srv.submit(image(70 + i), "gaussian3") for i in range(4)]
            for f in futs:
                f.result(120)
            stats = srv.stats()
        assert stats["compile"]["hits"] >= 1
        assert stats["compile"]["misses"] == 0   # every point was pre-warmed

    def test_plan_resolved_once_per_bucket(self, monkeypatch):
        """Steady-state dispatch does no tuning-cache re-resolution: the
        PlanConfig winner is resolved once per (bucket, traced n)."""
        from repro.serve import executor as executor_mod
        calls = []
        real = executor_mod.resolve_filter_plan

        def spy(*a, **kw):
            calls.append(a)
            return real(*a, **kw)

        monkeypatch.setattr(executor_mod, "resolve_filter_plan", spy)
        cfg = ServerConfig(max_batch=2, max_delay_ms=FAR)
        with ImageFilterServer(cfg) as srv:
            futs = [srv.submit(image(80 + i), "gaussian3") for i in range(6)]
            for f in futs:                       # three size-flushed batches
                f.result(120)
        assert len(calls) == 1                   # one bucket, one resolution

    def test_executor_warm_matches_submit_key(self):
        ex = BatchExecutor()
        key = ex.warm((16, 16), "box3", n=3)     # rounds to pow-2 like run
        assert key == serve_key(
            bucket_key("box3", "refmlm", "local", 8, 16, 16), 4)
        assert key in ex.warmed


# -------------------------------------------------- pipeline + tuning hooks

class TestPipelineHooks:
    def test_apply_filter_batch_matches_per_image(self):
        imgs = [image(10 + i) for i in range(3)]
        outs = apply_filter_batch(imgs, "sharpen3", method="refmlm")
        assert len(outs) == 3
        for im, out in zip(imgs, outs):
            np.testing.assert_array_equal(
                out, np.asarray(apply_filter(im, "sharpen3")))

    def test_apply_filter_batch_pad_to_is_invisible(self):
        imgs = [image(20 + i) for i in range(3)]
        plain = apply_filter_batch(imgs, "gaussian3")
        padded = apply_filter_batch(imgs, "gaussian3", pad_to=8)
        assert len(padded) == 3
        for a, b in zip(plain, padded):
            np.testing.assert_array_equal(a, b)

    def test_apply_filter_batch_rejects_mixed_shapes(self):
        with pytest.raises(ValueError):
            apply_filter_batch([image(1, (16, 16)), image(2, (24, 20))],
                               "gaussian3")

    def test_resolve_filter_blocks_pins_bit_identically(self):
        """Pinning the resolved grid explicitly (the serve hot path) gives
        the same bytes as letting apply_filter resolve per call."""
        imgs = np.stack([image(30 + i) for i in range(4)])
        for name in ("gaussian5", "laplacian"):      # fused + direct kinds
            n, h, w = imgs.shape
            cfg = resolve_filter_blocks(name, n, h, w)
            pinned = apply_filter(
                imgs, name, block_rows=cfg.block_rows,
                block_cols=w if cfg.block_cols is None else cfg.block_cols,
                batch_fold=cfg.batch_fold)
            np.testing.assert_array_equal(np.asarray(pinned),
                                          np.asarray(apply_filter(imgs, name)))

    def test_resolve_filter_plan_pins_bit_identically(self):
        """Pinning the full resolved plan explicitly (the §11 serve hot
        path) gives the same bytes as letting apply_filter resolve."""
        from repro.filters import resolve_filter_plan
        imgs = np.stack([image(40 + i) for i in range(4)])
        for name in ("gaussian5", "laplacian"):      # separable + direct
            n, h, w = imgs.shape
            plan = resolve_filter_plan(name, n, h, w)
            assert None not in (plan.block_rows, plan.block_cols,
                                plan.batch_fold)
            pinned = apply_filter(
                imgs, name, separable=plan.dataflow != "direct",
                fused=plan.dataflow == "fused",
                block_rows=plan.block_rows, block_cols=plan.block_cols,
                batch_fold=plan.batch_fold)
            np.testing.assert_array_equal(np.asarray(pinned),
                                          np.asarray(apply_filter(imgs, name)))

    def test_resolve_blocks_fully_explicit_fast_path(self):
        got = resolve_blocks("direct", 1, 32, 32, 3, 3,
                             block_rows=16, block_cols=32, batch_fold=False)
        assert got == BlockConfig(16, 32, False)

    def test_resolve_blocks_cached_agrees(self):
        args = ("fused", 4, 64, 64, 5, 5)
        assert resolve_blocks_cached(*args) == resolve_blocks(*args)
        assert resolve_blocks_cached(*args) is resolve_blocks_cached(*args)
