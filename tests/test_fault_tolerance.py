"""Fault-tolerance tests (DESIGN.md §12), driven by the deterministic
injection harness in `repro.runtime.fault`.

Covered: the injector's rule algebra (nth/every/key/index/poison) and its
legacy step API; bisect-and-retry failure isolation (only the poisoned
request fails, neighbors stay bit-identical); transient-blip recovery;
deadline shedding; the worker catch-all and the degraded-state /
fail-fast-admission surface; the per-bucket sharded->local fallback
ladder; crash-resume of `stream_filter` via the completed-tile journal
(a killed-then-resumed run is byte-identical to a cold one); and the
exactly-once / no-slot-leak invariants under randomized chaos schedules
(hypothesis, skipped when not installed).

Every schedule is a deterministic function of the probe stream -- no
random sleeps, no wall-clock races: the injector decides exactly which
dispatch, shard, or tile dies.
"""
import threading
import time

import numpy as np
import pytest

from repro.distribute import stream_filter
from repro.distribute.streamed import (
    JOURNAL_MAGIC,
    journal_fingerprint,
    load_journal,
)
from repro.filters import apply_filter
from repro.runtime.fault import (
    SITE_EXECUTE,
    SITE_SHARD,
    SITE_TILE,
    FaultInjector,
    InjectedFault,
    fault_scope,
    probe,
)
from repro.serve import (
    BatchExecutor,
    DeadlineExceeded,
    FilterFuture,
    FilterRequest,
    ImageFilterServer,
    MicroBatch,
    ServerConfig,
    ServerDegraded,
)

#: far-future flush deadline so only size/drain triggers fire
FAR = 3600_000.0


def image(seed: int, shape=(24, 20)) -> np.ndarray:
    """Unique per-seed payload -- cross-wired responses fail by value."""
    return np.random.default_rng(seed).integers(
        0, 256, shape).astype(np.int32)


def direct(img, filt="gaussian3", **kw) -> np.ndarray:
    return np.asarray(apply_filter(img, filt, **kw))


def settle(srv, timeout=10.0):
    """Wait for the worker's post-fulfilment accounting (stats, slot
    release) without closing the server: futures resolve slightly before
    the worker finishes the batch's bookkeeping."""
    deadline = time.monotonic() + timeout
    while srv._gate.inflight and time.monotonic() < deadline:
        time.sleep(0.005)


# ---------------------------------------------------------- the injector


class TestFaultInjector:
    def test_legacy_step_api_unchanged(self):
        inj = FaultInjector(fail_at_steps=[3])
        inj.check(2)
        with pytest.raises(InjectedFault):
            inj.check(3)
        inj.check(3)                    # fires once, restart continues

    def test_probe_is_noop_outside_scope(self):
        probe(SITE_EXECUTE, key="anything", seqs=(1, 2))

    def test_at_call_fires_exactly_nth(self):
        inj = FaultInjector().at_call(SITE_EXECUTE, 2)
        with fault_scope(inj):
            probe(SITE_EXECUTE)
            with pytest.raises(InjectedFault):
                probe(SITE_EXECUTE)
            probe(SITE_EXECUTE)         # times=1: transient blip
        assert inj.calls[SITE_EXECUTE] == 3
        assert len(inj.events) == 1 and inj.events[0][1] == 2

    def test_every_k_is_a_rate(self):
        inj = FaultInjector().every(SITE_TILE, 3)
        fired = 0
        with fault_scope(inj):
            for _ in range(9):
                try:
                    probe(SITE_TILE)
                except InjectedFault:
                    fired += 1
        assert fired == 3               # calls 3, 6, 9

    def test_on_key_substring_and_sites_are_independent(self):
        inj = FaultInjector().on_key(SITE_SHARD, "filter/exchange")
        with fault_scope(inj):
            probe(SITE_SHARD, key="conv2d/exchange")      # no match
            probe(SITE_EXECUTE, key="filter/exchange")    # wrong site
            with pytest.raises(InjectedFault):
                probe(SITE_SHARD, key="filter/exchange/x")
            with pytest.raises(InjectedFault):            # persistent
                probe(SITE_SHARD, key="filter/exchange/x")

    def test_at_index_half_open_range(self):
        inj = FaultInjector().at_index(SITE_TILE, 4, 6, times=None)
        hits = []
        with fault_scope(inj):
            for i in range(8):
                try:
                    probe(SITE_TILE, index=i)
                except InjectedFault:
                    hits.append(i)
        assert hits == [4, 5]

    def test_poison_matches_any_batch_holding_the_seq(self):
        inj = FaultInjector().poison(SITE_EXECUTE, 7)
        with fault_scope(inj):
            probe(SITE_EXECUTE, seqs=(1, 2, 3))
            with pytest.raises(InjectedFault):
                probe(SITE_EXECUTE, seqs=(5, 6, 7))
            with pytest.raises(InjectedFault):
                probe(SITE_EXECUTE, seqs=(7,))

    def test_scope_exit_deactivates(self):
        inj = FaultInjector().at_call(SITE_EXECUTE, 1)
        with fault_scope(inj):
            with pytest.raises(InjectedFault):
                probe(SITE_EXECUTE)
        probe(SITE_EXECUTE)


# ------------------------------------------- bisection failure isolation


class TestFailureIsolation:
    def test_poisoned_request_is_isolated(self):
        """One poisoned request in a coalesced batch of five: it alone
        fails, every neighbor is re-served bit-identically, the server
        stays healthy and leaks no slots."""
        imgs = [image(10 + i) for i in range(5)]
        cfg = ServerConfig(max_batch=5, max_delay_ms=FAR)
        inj = FaultInjector().poison(SITE_EXECUTE, 3)     # seqs are 1-based
        with fault_scope(inj), ImageFilterServer(cfg) as srv:
            futs = [srv.submit(im, "gaussian3") for im in imgs]
            srv.close(drain=True)
            stats = srv.stats()
            assert srv._gate.inflight == 0
        with pytest.raises(InjectedFault):
            futs[2].result(120)
        for i, fut in enumerate(futs):
            if i != 2:
                np.testing.assert_array_equal(
                    fut.result(120), direct(imgs[i]))
        assert stats["served"] == 4 and stats["failed"] == 1
        assert stats["isolated"] == 1 and stats["retries"] > 0
        # bisection is isolation, not degradation: the server stays healthy
        assert stats["healthy"] and stats["state"] == "healthy"
        assert stats["errors"] == 0

    def test_transient_blip_serves_everyone(self):
        """A one-shot dispatch fault: the bisected halves retry clean, so
        every request is served and nothing is isolated."""
        imgs = [image(30 + i) for i in range(4)]
        cfg = ServerConfig(max_batch=4, max_delay_ms=FAR)
        inj = FaultInjector().at_call(SITE_EXECUTE, 1)
        with fault_scope(inj), ImageFilterServer(cfg) as srv:
            futs = [srv.submit(im, "gaussian3") for im in imgs]
            srv.close(drain=True)
            stats = srv.stats()
        for im, fut in zip(imgs, futs):
            np.testing.assert_array_equal(fut.result(120), direct(im))
        assert stats["served"] == 4 and stats["failed"] == 0
        assert stats["isolated"] == 0 and stats["retries"] == 2
        assert stats["healthy"]

    def test_all_poisoned_all_isolated(self):
        imgs = [image(50 + i) for i in range(2)]
        cfg = ServerConfig(max_batch=2, max_delay_ms=FAR)
        inj = FaultInjector().poison(SITE_EXECUTE, 1, 2)
        with fault_scope(inj), ImageFilterServer(cfg) as srv:
            futs = [srv.submit(im, "gaussian3") for im in imgs]
            srv.close(drain=True)
            stats = srv.stats()
            assert srv._gate.inflight == 0
        for fut in futs:
            with pytest.raises(InjectedFault):
                fut.result(120)
        assert stats["isolated"] == 2 and stats["failed"] == 2
        assert stats["served"] == 0


class TestExecutorExactlyOnce:
    def _batch(self, n: int, seq0: int = 1) -> tuple[MicroBatch, list]:
        reqs = tuple(
            FilterRequest(img=image(seq0 + i), filt="gaussian3",
                          method="refmlm", exec="local",
                          nbits=8, future=FilterFuture(), submitted=0.0,
                          seq=seq0 + i)
            for i in range(n))
        return MicroBatch(reqs[0].key, reqs, "size"), list(reqs)

    def test_run_never_raises_when_datapath_always_raises(self, monkeypatch):
        """Even a hard-broken datapath resolves every future exactly once
        (all isolated), and run() itself never raises."""
        import repro.serve.workload as wl_mod

        def boom(*a, **kw):
            raise RuntimeError("datapath down")

        monkeypatch.setattr(wl_mod, "apply_filter_batch", boom)
        ex = BatchExecutor()
        batch, reqs = self._batch(3)
        ex.run(batch)                   # must not raise
        for r in reqs:
            assert r.future.done() and r.future.failed()
            with pytest.raises(RuntimeError):
                r.future.result(0)
        assert ex.isolated == 3

    def test_run_tolerates_pre_resolved_future(self):
        """A future already fulfilled (a §12 race the done() guards absorb)
        neither double-fulfils nor starves its batchmates."""
        ex = BatchExecutor()
        batch, reqs = self._batch(2)
        sentinel = np.zeros((24, 20), np.uint8)
        reqs[0].future.set_result(sentinel)
        ex.run(batch)
        assert reqs[0].future.result(0) is sentinel       # untouched
        np.testing.assert_array_equal(
            reqs[1].future.result(0), direct(reqs[1].img))


# ----------------------------------------------------- deadline shedding


class TestDeadlineShedding:
    def test_expired_request_is_shed_not_dispatched(self):
        cfg = ServerConfig(max_batch=8, max_delay_ms=FAR)
        inj = FaultInjector()           # rule-free: pure probe counter
        with fault_scope(inj), ImageFilterServer(cfg) as srv:
            fut = srv.submit(image(1), "gaussian3", deadline_ms=0.0)
            with pytest.raises(DeadlineExceeded):
                fut.result(120)
            srv.close(drain=True)                 # settle worker accounting
            stats = srv.stats()
            assert srv._gate.inflight == 0        # slot released on shed
        assert stats["shed"] == 1 and stats["served"] == 0
        assert stats["batches"] == 0              # never burned a dispatch
        assert inj.calls.get(SITE_EXECUTE, 0) == 0

    def test_live_requests_unaffected_by_shed_neighbor(self):
        cfg = ServerConfig(max_batch=2, max_delay_ms=FAR)
        with ImageFilterServer(cfg) as srv:
            dead = srv.submit(image(1), "gaussian3", deadline_ms=0.0)
            with pytest.raises(DeadlineExceeded):
                dead.result(120)
            live = [srv.submit(image(2 + i), "gaussian3") for i in range(2)]
            srv.close(drain=True)
            stats = srv.stats()
        for i, fut in enumerate(live):
            np.testing.assert_array_equal(fut.result(120),
                                          direct(image(2 + i)))
        assert stats["shed"] == 1 and stats["served"] == 2

    def test_default_deadline_from_config(self):
        cfg = ServerConfig(max_batch=8, max_delay_ms=FAR,
                           default_deadline_ms=0.0)
        with ImageFilterServer(cfg) as srv:
            fut = srv.submit(image(1), "gaussian3")
            with pytest.raises(DeadlineExceeded):
                fut.result(120)
            srv.close(drain=True)
            assert srv.stats()["shed"] == 1


# ------------------------------------- worker catch-all + degraded state


class TestWorkerCatchAll:
    def test_serving_layer_bug_degrades_not_hangs(self):
        """An error escaping the executor's own isolation (a serving-layer
        bug) fails that batch's futures, releases its slots, records the
        error, and flips the health surface -- the worker survives."""
        cfg = ServerConfig(max_batch=2, max_delay_ms=FAR)
        with ImageFilterServer(cfg) as srv:
            def broken_run(batch):
                raise RuntimeError("serving-layer bug")
            srv._executor.run = broken_run
            futs = [srv.submit(image(i), "gaussian3") for i in range(2)]
            for fut in futs:
                with pytest.raises(RuntimeError, match="serving-layer bug"):
                    fut.result(120)
            settle(srv)
            assert srv._gate.inflight == 0
            stats = srv.stats()
            assert stats["errors"] == 1
            assert "serving-layer bug" in stats["last_error"]
            assert not stats["healthy"] and stats["state"] == "degraded"
            # the worker is still alive and serving
            del srv._executor.run           # restore the real method
            futs2 = [srv.submit(image(10 + i), "gaussian3") for i in range(2)]
            for i, fut in enumerate(futs2):
                np.testing.assert_array_equal(fut.result(120),
                                              direct(image(10 + i)))
            settle(srv)
            assert srv.stats()["served"] == 2

    def test_fail_fast_degraded_refuses_admission(self):
        cfg = ServerConfig(max_batch=2, max_delay_ms=FAR,
                           fail_fast_degraded=True)
        with ImageFilterServer(cfg) as srv:
            def broken_run(batch):
                raise RuntimeError("bug")
            srv._executor.run = broken_run
            futs = [srv.submit(image(i), "gaussian3") for i in range(2)]
            for fut in futs:
                with pytest.raises(RuntimeError):
                    fut.result(120)
            settle(srv)
            with pytest.raises(ServerDegraded):
                srv.submit(image(9), "gaussian3")
            stats = srv.stats()
            assert stats["fast_failed"] == 1
            assert srv._gate.inflight == 0        # no slot taken on fast-fail


# ------------------------------------- scale-out degradation ladder (§12)


class TestDegradedFallback:
    def test_sharded_bucket_falls_back_to_local(self):
        """A persistently failing sharded dispatch trips the bucket into
        the bit-identical local fallback: every request is still served
        with the right bytes, and the server reports degraded."""
        imgs = [image(70 + i) for i in range(2)]
        cfg = ServerConfig(max_batch=2, max_delay_ms=FAR, exec="sharded",
                           degrade_after=1)
        inj = FaultInjector().on_key(SITE_SHARD, "filter/")
        with fault_scope(inj), ImageFilterServer(cfg) as srv:
            futs = [srv.submit(im, "gaussian3") for im in imgs]
            for im, fut in zip(imgs, futs):
                np.testing.assert_array_equal(fut.result(120), direct(im))
            # next batch routes straight to the pinned local fallback
            futs2 = [srv.submit(im, "gaussian3") for im in imgs]
            for im, fut in zip(imgs, futs2):
                np.testing.assert_array_equal(fut.result(120), direct(im))
            srv.close(drain=True)             # settle worker accounting
            stats = srv.stats()
            assert srv._gate.inflight == 0
        assert stats["served"] == 4 and stats["failed"] == 0
        assert not stats["healthy"] and stats["state"] == "degraded"
        assert sum(stats["degraded"].values()) == 2   # both fallback runs
        assert sum(stats["dispatch_failures"].values()) == 1
        assert inj.calls[SITE_SHARD] >= 1             # the fault really fired

    def test_transient_shard_fault_does_not_degrade(self):
        """With degrade_after=2, a single shard blip is absorbed by the
        bisection retry and the bucket stays on the scale-out path."""
        imgs = [image(80 + i) for i in range(2)]
        cfg = ServerConfig(max_batch=2, max_delay_ms=FAR, exec="sharded",
                           degrade_after=2)
        inj = FaultInjector().at_call(SITE_SHARD, 1)
        with fault_scope(inj), ImageFilterServer(cfg) as srv:
            futs = [srv.submit(im, "gaussian3") for im in imgs]
            for im, fut in zip(imgs, futs):
                np.testing.assert_array_equal(fut.result(120), direct(im))
            srv.close(drain=True)             # settle worker accounting
            stats = srv.stats()
        assert stats["served"] == 2 and stats["healthy"]
        assert stats["degraded"] == {}
        assert stats["retries"] > 0               # bisection did the saving


# ------------------------------------------------- stream crash-resume


class TestStreamCrashResume:
    SHAPE = (48, 40)
    TILE = (16, 16)

    def _src(self):
        return np.random.default_rng(5).integers(
            0, 256, self.SHAPE).astype(np.int32)

    def test_killed_then_resumed_is_byte_identical(self, tmp_path):
        src = self._src()
        cold = np.asarray(stream_filter(src, "gaussian3", tile=self.TILE,
                                        tile_batch=2))
        out = np.memmap(tmp_path / "out.u8", np.uint8, "w+",
                        shape=self.SHAPE)
        # 9 tiles in batches of 2; kill the run at tile index 7 (group 4)
        inj = FaultInjector().at_index(SITE_TILE, 7)
        with fault_scope(inj), pytest.raises(InjectedFault):
            stream_filter(src, "gaussian3", tile=self.TILE, tile_batch=2,
                          out=out)
        jpath = tmp_path / "out.u8.journal"
        fp = journal_fingerprint(self.SHAPE, "gaussian3", *self.TILE, {})
        done = load_journal(jpath, fp)
        assert done == {0, 1, 2, 3, 4, 5}         # 3 full groups journaled
        counter = FaultInjector()                 # rule-free probe counter
        with fault_scope(counter):
            res = stream_filter(src, "gaussian3", tile=self.TILE,
                                tile_batch=2, out=out, resume=True)
        np.testing.assert_array_equal(np.asarray(res), cold)
        assert counter.calls[SITE_TILE] == 3      # only the 3 missing tiles
        assert load_journal(jpath, fp) == set(range(9))

    def test_resume_with_complete_journal_recomputes_nothing(self, tmp_path):
        src = self._src()
        out = np.memmap(tmp_path / "o.u8", np.uint8, "w+", shape=self.SHAPE)
        stream_filter(src, "gaussian3", tile=self.TILE, out=out)
        counter = FaultInjector()
        with fault_scope(counter):
            stream_filter(src, "gaussian3", tile=self.TILE, out=out,
                          resume=True)
        assert counter.calls.get(SITE_TILE, 0) == 0

    def test_fresh_run_truncates_stale_journal(self, tmp_path):
        src = self._src()
        out = np.memmap(tmp_path / "o.u8", np.uint8, "w+", shape=self.SHAPE)
        jpath = tmp_path / "o.u8.journal"
        jpath.write_text(f"{JOURNAL_MAGIC} bogus-fingerprint\n0\n1\n")
        stream_filter(src, "gaussian3", tile=self.TILE, out=out)
        fp = journal_fingerprint(self.SHAPE, "gaussian3", *self.TILE, {})
        assert load_journal(jpath, fp) == set(range(9))

    def test_journal_guards(self, tmp_path):
        fp = journal_fingerprint(self.SHAPE, "gaussian3", *self.TILE, {})
        missing = tmp_path / "nope.journal"
        assert load_journal(missing, fp) == set()
        torn = tmp_path / "torn.journal"
        torn.write_text(f"{JOURNAL_MAGIC} {fp}\n0\n1\n2")   # no trailing \n
        assert load_journal(torn, fp) == {0, 1, 2}
        torn.write_text(f"{JOURNAL_MAGIC} {fp}\n0\n1\n17")
        assert 17 in load_journal(torn, fp)       # complete digits count
        torn.write_text(f"{JOURNAL_MAGIC} {fp}\n0\n1\n1x")  # torn mid-digit
        assert load_journal(torn, fp) == {0, 1}
        bad = tmp_path / "bad.journal"
        bad.write_text("not a journal\n0\n")
        with pytest.raises(ValueError, match="not a"):
            load_journal(bad, fp)
        other = tmp_path / "other.journal"
        wrong_fp = journal_fingerprint(self.SHAPE, "sobel_x", *self.TILE, {})
        other.write_text(f"{JOURNAL_MAGIC} {wrong_fp}\n0\n")
        with pytest.raises(ValueError, match="different stream plan"):
            load_journal(other, fp)

    def test_resume_requires_out_and_journal(self, tmp_path):
        src = self._src()
        with pytest.raises(ValueError, match="resume=True needs"):
            stream_filter(src, "gaussian3", tile=self.TILE, resume=True)
        with pytest.raises(ValueError, match="resume=True needs journal"):
            stream_filter(src, "gaussian3", tile=self.TILE,
                          out=np.empty(self.SHAPE, np.uint8), resume=True)

    def test_resume_mismatched_plan_refuses(self, tmp_path):
        src = self._src()
        out = np.memmap(tmp_path / "o.u8", np.uint8, "w+", shape=self.SHAPE)
        stream_filter(src, "gaussian3", tile=self.TILE, out=out)
        with pytest.raises(ValueError, match="different stream plan"):
            stream_filter(src, "sobel_x", tile=self.TILE, out=out,
                          resume=True)

    def test_pipeline_plumbs_journal_and_resume(self, tmp_path):
        """`apply_filter(exec='streamed', journal=, resume=)` is the same
        crash-resume surface; local/sharded modes reject the arguments."""
        src = self._src()
        jpath = tmp_path / "j.journal"
        out = np.empty(self.SHAPE, np.uint8)
        inj = FaultInjector().at_index(SITE_TILE, 4)
        with fault_scope(inj), pytest.raises(InjectedFault):
            apply_filter(src, "gaussian3", exec="streamed", tile=self.TILE,
                         out=out, journal=str(jpath))
        res = apply_filter(src, "gaussian3", exec="streamed", tile=self.TILE,
                           out=out, journal=str(jpath), resume=True)
        np.testing.assert_array_equal(np.asarray(res), direct(src))
        with pytest.raises(ValueError, match="journal/resume"):
            apply_filter(src, "gaussian3", journal=str(jpath))
        with pytest.raises(ValueError, match="journal/resume"):
            apply_filter(src, "gaussian3", resume=True)
        with pytest.raises(ValueError, match="streamed-mode"):
            apply_filter(src, "gaussian3", exec="sharded",
                         journal=str(jpath))


# --------------------------------------------------- chaos property test


def test_chaos_schedule_exactly_once_no_leaks():
    """Property: under any poison set and submission order, every future
    resolves exactly once, no admission slot leaks, poisoned requests get
    the injected fault, and every success is bit-identical to the direct
    call."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    shapes = [(16, 16), (24, 20)]

    @settings(max_examples=15, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 1), st.booleans()),
                    min_size=1, max_size=12),
           st.integers(1, 4))
    def run(reqspec, max_batch):
        poisoned = {i + 1 for i, (_, bad) in enumerate(reqspec) if bad}
        inj = FaultInjector()
        if poisoned:
            inj.poison(SITE_EXECUTE, *poisoned)
        cfg = ServerConfig(max_batch=max_batch, max_delay_ms=FAR)
        with fault_scope(inj), ImageFilterServer(cfg) as srv:
            futs = []
            for i, (si, _) in enumerate(reqspec):
                im = image(i, shapes[si])
                futs.append((i, im, srv.submit(im, "gaussian3")))
            srv.close(drain=True)
            stats = srv.stats()
            assert srv._gate.inflight == 0            # no slot leaked
        for i, im, fut in futs:
            assert fut.done()                         # exactly-once: resolved
            if (i + 1) in poisoned:
                with pytest.raises(InjectedFault):
                    fut.result(0)
            else:
                np.testing.assert_array_equal(fut.result(0), direct(im))
        assert stats["served"] == len(reqspec) - len(poisoned)
        assert stats["failed"] == len(poisoned)
        assert stats["isolated"] == len(poisoned)

    run()


# -------------------------------------------- concurrent chaos (threads)


def test_concurrent_submissions_with_faults():
    """Racing client threads while a poison rule is live: the exactly-once
    and slot-accounting invariants hold under real concurrency too."""
    per_thread, n_threads = 6, 3
    total = per_thread * n_threads
    poisoned_seqs = {3, 7, 11}
    inj = FaultInjector().poison(SITE_EXECUTE, *poisoned_seqs)
    cfg = ServerConfig(max_batch=4, max_delay_ms=5.0, max_pending=64)
    outcomes: dict[int, tuple] = {}
    lock = threading.Lock()

    def client(tid: int, srv: ImageFilterServer):
        futs = []
        for j in range(per_thread):
            uid = tid * per_thread + j
            im = image(uid, (16, 16))
            futs.append((uid, im, srv.submit(im, "gaussian3")))
        for uid, im, fut in futs:
            try:
                out = fut.result(120)
                with lock:
                    outcomes[uid] = ("ok", im, out)
            except InjectedFault:
                with lock:
                    outcomes[uid] = ("fault", im, None)

    with fault_scope(inj), ImageFilterServer(cfg) as srv:
        threads = [threading.Thread(target=client, args=(t, srv))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        srv.close(drain=True)
        stats = srv.stats()
        assert srv._gate.inflight == 0
    assert len(outcomes) == total
    n_fault = sum(1 for kind, *_ in outcomes.values() if kind == "fault")
    assert n_fault == len(poisoned_seqs)
    for kind, im, out in outcomes.values():
        if kind == "ok":
            np.testing.assert_array_equal(out, direct(im))
    assert stats["served"] == total - n_fault
    assert stats["failed"] == n_fault == stats["isolated"]
    assert stats["healthy"]
