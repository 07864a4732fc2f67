"""Service levels in the serving layer (DESIGN.md §13): the adaptive
batching controller, weighted admission + per-tenant quotas, priority
flush/shed ordering, the LRU plan memo, and the elastic executor pool.

The §10 invariant these features must never touch is asserted throughout:
every served output is bit-identical to a direct `apply_filter` call no
matter what flush size the controller picked, which priority class the
request rode, or which pool member (or rebuilt mesh) served it.

Pure policy (controller maths, batcher ordering, gate accounting) runs on
fake clocks; end-to-end behaviour runs a real `ImageFilterServer` on the
single CPU device with the §12 deterministic injector driving failures.
"""
import threading

import jax
import numpy as np
import pytest

pytest.importorskip("jax")

from repro.filters import apply_filter  # noqa: E402
from repro.runtime.fault import (  # noqa: E402
    SITE_EXECUTE,
    FaultInjector,
    fault_scope,
)
from repro.serve import (  # noqa: E402
    AdmissionGate,
    BatchExecutor,
    ImageFilterServer,
    ServerConfig,
    ServerOverloaded,
    ShapeBucketedBatcher,
    TenantOverQuota,
    request_weight,
)
from repro.serve.controller import AdaptiveBatchController  # noqa: E402
from repro.serve.pool import rendezvous_score  # noqa: E402
from repro.serve.request import (  # noqa: E402
    FilterFuture,
    FilterRequest,
    bucket_key,
)

FAR = 3600e3        # "never fires" flush delay, in ms
RNG = np.random.default_rng(11)


def image(seed: int, shape=(32, 32)) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 256, shape, np.uint8)


class Clock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t


def mk_req(seq: int, *, shape=(32, 32), filt="gaussian3",
           priority="normal", slo=None, submitted=0.0,
           deadline=None) -> FilterRequest:
    h, w = shape
    return FilterRequest(img=image(seq, shape), filt=filt, method="refmlm",
                         mult_impl="auto", exec="local", nbits=8,
                         future=FilterFuture(), submitted=submitted, seq=seq,
                         deadline=deadline, priority=priority,
                         slo=slo, weight=request_weight(h, w))


# ------------------------------------------------------------- controller

class TestController:
    def test_no_slo_falls_back_to_static_pair(self):
        c = AdaptiveBatchController(8, 0.5)
        q = (mk_req(1), mk_req(2))
        assert c.params("k", q) == (8, 0.5)
        assert c.stats()["static_decisions"] == 1

    def test_converges_to_largest_batch_fitting_the_budget(self):
        """With an observed ledger of s(n)=n seconds and a 4.5 s budget,
        the controller flushes at 4 and spends the leftover 0.5 s
        collecting."""
        c = AdaptiveBatchController(8, 10.0, safety=1.0, alpha=1.0)
        key = "k"
        anchor = mk_req(0)
        for n in (1, 2, 4, 8):
            c.observe(key, anchor, n, float(n))
        q = (mk_req(1, slo=4.5, submitted=0.0),)
        size, delay = c.params(key, q)
        assert size == 4
        assert delay == pytest.approx(0.5)
        assert c.stats()["chosen"][key] == 4

    def test_spent_wait_shrinks_the_budget(self):
        """The budget is measured from the oldest request's submission:
        a request that already waited gets a smaller batch, not a blown
        SLO."""
        c = AdaptiveBatchController(8, 10.0, safety=1.0, alpha=1.0)
        for n in (1, 2, 4, 8):
            c.observe("k", mk_req(0), n, float(n))
        tight = c.params("k", (mk_req(1, slo=4.5, submitted=2.4),))
        assert tight[0] == 2          # 2.1 s left -> only s(2)=2 fits
        assert c.params("k", (mk_req(2, slo=4.5, submitted=4.4),))[0] == 1

    def test_observation_interpolates_across_the_ladder(self):
        """One observed size anchors the whole pow-2 ladder by model-cost
        ratio: predictions stay monotone in n."""
        c = AdaptiveBatchController(8, 10.0)
        req = mk_req(0)
        c.observe("k", req, 4, 0.04)
        p2, p4, p8 = (c.predict_s("k", req, n) for n in (2, 4, 8))
        assert p4 == pytest.approx(0.04)
        assert p2 <= p4 <= p8

    def test_ewma_tracks_drift(self):
        c = AdaptiveBatchController(8, 10.0, alpha=0.5)
        req = mk_req(0)
        c.observe("k", req, 1, 1.0)
        c.observe("k", req, 1, 3.0)
        assert c.predict_s("k", req, 1) == pytest.approx(2.0)

    def test_safety_margin_narrows_the_choice(self):
        c = AdaptiveBatchController(8, 10.0, safety=2.0, alpha=1.0)
        for n in (1, 2, 4, 8):
            c.observe("k", mk_req(0), n, float(n))
        # 2*s(4)=8 > 4.5 budget, 2*s(2)=4 fits
        assert c.params("k", (mk_req(1, slo=4.5),))[0] == 2


class TestBatcherPolicyHook:
    def test_policy_narrows_flush_size_and_delay(self):
        clk = Clock()
        b = ShapeBucketedBatcher(8, 1.0, clk, policy=lambda k, q: (2, 0.0))
        for i in range(3):
            b.add(mk_req(i))
        got = b.ready(0.0)
        assert [len(g.requests) for g in got] == [2, 1]
        assert got[0].reason == "size"

    def test_policy_is_clamped_by_the_static_ceiling(self):
        clk = Clock()
        b = ShapeBucketedBatcher(4, 1.0, clk, policy=lambda k, q: (100, 99.0))
        for i in range(5):
            b.add(mk_req(i))
        got = b.ready(0.0)
        assert len(got[0].requests) == 4      # size clamped to max_batch
        assert b.next_deadline() == pytest.approx(1.0)  # delay clamped


# ------------------------------------------- priorities, weights, quotas

class TestPriorityOrdering:
    def test_high_buckets_flush_before_low(self):
        clk = Clock()
        b = ShapeBucketedBatcher(2, FAR / 1e3, clk)
        for i, pri in enumerate(("low", "low", "high", "high", "normal",
                                 "normal")):
            b.add(mk_req(i, priority=pri))
        got = b.ready(0.0)
        assert [g.requests[0].priority for g in got] == ["high", "normal",
                                                         "low"]

    def test_overload_shed_takes_low_newest_first_never_high(self):
        clk = Clock()
        b = ShapeBucketedBatcher(8, FAR / 1e3, clk)
        for i, pri in enumerate(("high", "high", "normal", "normal", "low",
                                 "low")):
            b.add(mk_req(i, priority=pri))
        freed = b.shed_overload(3)
        assert freed == 3
        shed = b.take_shed()
        assert all(s.cause == "overload" for s in shed)
        # both lows go (newest first), then one normal; high untouched
        assert [s.request.seq for s in shed] == [5, 4, 3]
        assert b.pending == 3
        freed = b.shed_overload(10)       # only high + 1 normal left
        assert freed == 1                 # the last normal; high protected
        assert b.pending == 2

    def test_request_weight_scales_with_pixels(self):
        assert request_weight(128, 128) == 1
        assert request_weight(64, 64) == 1
        assert request_weight(256, 256) == 4
        assert request_weight(129, 128) == 2


class TestWeightedGate:
    def test_weighted_slots_bound_admission(self):
        clk = Clock()
        g = AdmissionGate(4, 0.0, clk)
        g.acquire(4)
        with pytest.raises(ServerOverloaded):
            g.acquire(1)
        g.release(4)
        g.acquire(1)

    def test_tenant_quota_isolates_tenants(self):
        clk = Clock()
        g = AdmissionGate(8, 0.0, clk, tenant_quota=2)
        g.acquire(2, tenant="bulk")
        with pytest.raises(TenantOverQuota):
            g.acquire(1, tenant="bulk")
        g.acquire(2, tenant="latency")        # other tenant unaffected
        stats = g.tenant_stats()
        assert stats["bulk"] == {"inflight": 2, "quota": 2, "rejected": 1}
        assert stats["latency"]["inflight"] == 2

    def test_oversized_weight_fails_loud(self):
        g = AdmissionGate(8, 10.0, Clock(), tenant_quota=2)
        with pytest.raises(TenantOverQuota, match="outright"):
            g.acquire(3, tenant="t")

    def test_on_wait_reports_the_blocked_weight(self):
        clk = Clock()
        seen = []
        g = AdmissionGate(2, 0.0, clk, on_wait=seen.append)
        g.acquire(2)
        with pytest.raises(ServerOverloaded):
            g.acquire(2)
        assert seen == [2]


# ----------------------------------------------------- end-to-end server

class TestServerServiceLevels:
    def test_adaptive_server_stays_bit_identical(self):
        cfg = ServerConfig(max_batch=4, max_delay_ms=5.0, adaptive=True)
        with ImageFilterServer(cfg) as srv:
            futs = [(srv.submit(image(i), "gaussian5", priority=p,
                                slo_ms=500.0), i)
                    for i, p in enumerate(("high", "normal", "low") * 3)]
            for fut, i in futs:
                np.testing.assert_array_equal(
                    fut.result(60),
                    np.asarray(apply_filter(image(i), "gaussian5")))
            st = srv.stats()
        assert st["controller"]["decisions"] > 0
        assert all(n <= cfg.max_batch
                   for n in st["controller"]["chosen"].values())
        assert st["served_priority"]["high"] == 3

    def test_overload_sheds_low_to_admit_new_work(self):
        """A blocked admission wakes the worker, which sheds the newest
        queued low-priority request (`ServerOverloaded` on its future);
        the freed slot admits the blocked submitter."""
        cfg = ServerConfig(max_batch=64, max_delay_ms=FAR, max_pending=2,
                           overload_shed=True, admission_timeout_s=10.0)
        srv = ImageFilterServer(cfg)
        try:
            f_old = srv.submit(image(1), "gaussian3", priority="low")
            f_new = srv.submit(image(2), "gaussian3", priority="low")
            f_high = srv.submit(image(3), "gaussian3", priority="high")
        finally:
            srv.close(drain=True)
        with pytest.raises(ServerOverloaded):
            f_new.result(5)               # newest low was shed
        np.testing.assert_array_equal(
            f_old.result(5), np.asarray(apply_filter(image(1), "gaussian3")))
        np.testing.assert_array_equal(
            f_high.result(5), np.asarray(apply_filter(image(3), "gaussian3")))
        st = srv.stats()
        assert st["shed_overload"] == 1 and st["served"] == 2

    def test_high_priority_is_never_overload_shed(self):
        cfg = ServerConfig(max_batch=64, max_delay_ms=FAR, max_pending=2,
                           overload_shed=True, admission_timeout_s=0.3)
        srv = ImageFilterServer(cfg)
        try:
            f1 = srv.submit(image(1), "gaussian3", priority="high")
            f2 = srv.submit(image(2), "gaussian3", priority="high")
            with pytest.raises(ServerOverloaded):
                srv.submit(image(3), "gaussian3", priority="high")
        finally:
            srv.close(drain=True)
        for f, i in ((f1, 1), (f2, 2)):
            np.testing.assert_array_equal(
                f.result(5), np.asarray(apply_filter(image(i), "gaussian3")))
        assert srv.stats()["shed_overload"] == 0

    def test_tenant_quota_end_to_end(self):
        cfg = ServerConfig(max_batch=64, max_delay_ms=FAR, max_pending=8,
                           tenant_quotas={"bulk": 1},
                           admission_timeout_s=0.2)
        srv = ImageFilterServer(cfg)
        try:
            f_bulk = srv.submit(image(1), "gaussian3", tenant="bulk")
            with pytest.raises(TenantOverQuota):
                srv.submit(image(2), "gaussian3", tenant="bulk")
            f_other = srv.submit(image(3), "gaussian3", tenant="fast")
        finally:
            srv.close(drain=True)
        assert f_bulk.result(5) is not None
        assert f_other.result(5) is not None

    def test_weighted_admission_counts_pixels(self):
        """One 256x256 frame (weight 4) fills a max_pending=4 server."""
        cfg = ServerConfig(max_batch=64, max_delay_ms=FAR, max_pending=4,
                           admission_timeout_s=0.2)
        srv = ImageFilterServer(cfg)
        try:
            big = srv.submit(image(1, (256, 256)), "gaussian3")
            with pytest.raises(ServerOverloaded):
                srv.submit(image(2), "gaussian3")
        finally:
            srv.close(drain=True)
        np.testing.assert_array_equal(
            big.result(10),
            np.asarray(apply_filter(image(1, (256, 256)), "gaussian3")))

    def test_slo_is_soft_deadline_is_hard(self):
        """A blown `slo_ms` still serves (it only shapes batching); a
        blown `deadline_ms` sheds."""
        cfg = ServerConfig(max_batch=8, max_delay_ms=20.0, adaptive=True)
        with ImageFilterServer(cfg) as srv:
            fut = srv.submit(image(1), "gaussian3", slo_ms=1e-3)
            out = fut.result(30)
        np.testing.assert_array_equal(
            out, np.asarray(apply_filter(image(1), "gaussian3")))


# ------------------------------------------------------- LRU plan memo

class TestPlanMemoLRU:
    def test_eviction_and_counters(self):
        ex = BatchExecutor(plan_memo_max=2)
        shapes = [(32, 32), (48, 48), (64, 64)]
        for h, w in shapes:
            ex._plan("gaussian3", "refmlm", "auto", 1, h, w)
        pm = ex.stats()["plan_memo"]
        assert pm == {"size": 2, "max": 2, "hits": 0, "misses": 3,
                      "evicts": 1}
        ex._plan("gaussian3", "refmlm", "auto", 1, 64, 64)   # still resident
        assert ex.stats()["plan_memo"]["hits"] == 1
        ex._plan("gaussian3", "refmlm", "auto", 1, 32, 32)   # was evicted
        pm = ex.stats()["plan_memo"]
        assert pm["misses"] == 4 and pm["evicts"] == 2 and pm["size"] == 2

    def test_lru_keeps_the_hot_entry(self):
        ex = BatchExecutor(plan_memo_max=2)
        ex._plan("gaussian3", "refmlm", "auto", 1, 32, 32)
        ex._plan("gaussian3", "refmlm", "auto", 1, 48, 48)
        ex._plan("gaussian3", "refmlm", "auto", 1, 32, 32)   # touch -> MRU
        ex._plan("gaussian3", "refmlm", "auto", 1, 64, 64)   # evicts 48
        assert ex.stats()["plan_memo"]["evicts"] == 1
        ex._plan("gaussian3", "refmlm", "auto", 1, 32, 32)
        assert ex.stats()["plan_memo"]["hits"] == 2


# ------------------------------------------------------------------ pool

def routed_member(filt: str, members=("m0", "m1"), exec_mode="sharded",
                  shape=(32, 32)) -> str:
    h, w = shape
    key = bucket_key(filt, "refmlm", "auto", exec_mode, 8, h, w, "normal")
    return max(members, key=lambda m: rendezvous_score(m, key))


class TestExecutorPool:
    def test_rendezvous_is_stable_under_member_removal(self):
        """Removing one member re-routes only that member's keys."""
        keys = [bucket_key(f"f{i}", "refmlm", "auto", "local", 8, 32, 32)
                for i in range(60)]
        full = {k: max(("m0", "m1", "m2"),
                       key=lambda m: rendezvous_score(m, k)) for k in keys}
        less = {k: max(("m0", "m1"),
                       key=lambda m: rendezvous_score(m, k)) for k in keys}
        assert any(v == "m2" for v in full.values())
        for k in keys:
            if full[k] != "m2":
                assert less[k] == full[k]

    def test_pool_serves_bit_identically(self):
        cfg = ServerConfig(max_batch=4, max_delay_ms=5.0, pool=((0,), (0,)))
        with ImageFilterServer(cfg) as srv:
            futs = [(srv.submit(image(i), f), f, i)
                    for i in range(4) for f in ("gaussian3", "sharpen3")]
            for fut, f, i in futs:
                np.testing.assert_array_equal(
                    fut.result(60), np.asarray(apply_filter(image(i), f)))
            st = srv.stats()
        assert st["pool"]["active"] == 2 and st["healthy"]

    def test_failing_member_is_retired_and_buckets_rebalance(self):
        """Kill one member's scale-out mesh: its §12 local fallback covers
        the detection window bit-identically, the pool retires it, and
        later traffic re-rendezvouses onto the survivor -- the server
        ends healthy."""
        filt = "gaussian3"
        target = routed_member(filt)
        cfg = ServerConfig(max_batch=2, max_delay_ms=2.0, exec="sharded",
                           pool=((0,), (0,)), drain_after=2, degrade_after=1)
        want = np.asarray(apply_filter(image(7), filt, exec="sharded"))
        inj = FaultInjector().on_key(SITE_EXECUTE,
                                     f"exec=sharded|member={target}")
        with fault_scope(inj):
            with ImageFilterServer(cfg) as srv:
                outs = [srv.submit(image(7), filt).result(120)
                        for _ in range(6)]
                st = srv.stats()
        for out in outs:
            np.testing.assert_array_equal(out, want)
        members = st["pool"]["members"]
        assert members[target]["state"] == "dead"
        survivor = "m1" if target == "m0" else "m0"
        assert members[survivor]["state"] == "active"
        assert members[survivor]["routes"] > 0
        assert st["pool"]["drains"] == 1
        assert st["healthy"] and st["served"] == 6

    def test_last_member_is_never_drained(self):
        """A single-member pool refuses the drain and survives on the §12
        local fallback (the server reports degraded, not dead)."""
        cfg = ServerConfig(max_batch=2, max_delay_ms=2.0, exec="sharded",
                           pool=((0,),), drain_after=2, degrade_after=1)
        want = np.asarray(apply_filter(image(9), "gaussian3"))
        inj = FaultInjector().on_key(SITE_EXECUTE, "exec=sharded|member=m0")
        with fault_scope(inj):
            with ImageFilterServer(cfg) as srv:
                outs = [srv.submit(image(9), "gaussian3").result(120)
                        for _ in range(4)]
                st = srv.stats()
        for out in outs:
            np.testing.assert_array_equal(out, want)
        assert st["pool"]["members"]["m0"]["state"] == "active"
        assert st["pool"]["drain_refused"] >= 1
        assert st["state"] == "degraded"      # pinned fallback, by design

    def test_local_member_dispatch_runs_on_its_own_device(self, monkeypatch):
        """A pool member's local dispatches (and its warmup) run under its
        own first device, not the process default -- one-chip members
        each use their own chip."""
        from repro.serve.workload import FilterWorkload
        seen = []
        real = FilterWorkload.execute

        def spy(self, executor, *a, **k):
            seen.append(jax.config.jax_default_device)
            return real(self, executor, *a, **k)

        monkeypatch.setattr(FilterWorkload, "execute", spy)
        dev = jax.devices()[0]
        cfg = ServerConfig(max_batch=2, max_delay_ms=2.0, pool=((dev.id,),))
        with ImageFilterServer(cfg) as srv:
            out = srv.submit(image(5), "gaussian3").result(60)
        np.testing.assert_array_equal(
            out, np.asarray(apply_filter(image(5), "gaussian3")))
        assert seen == [dev]
        seen.clear()
        # no device subset: the process default
        with ImageFilterServer(ServerConfig(max_batch=2,
                                            max_delay_ms=2.0)) as srv:
            srv.submit(image(5), "gaussian3").result(60)
        assert seen == [None]

    def test_pool_warmup_routes_to_the_serving_member(self):
        cfg = ServerConfig(max_batch=4, max_delay_ms=5.0, pool=((0,), (0,)))
        with ImageFilterServer(cfg) as srv:
            keys = srv.warmup(shapes=[(32, 32)],
                              filters=["gaussian3", "sharpen3"])
            assert len(keys) == 2
            fut = srv.submit(image(3), "gaussian3")
            fut.result(60)
            st = srv.stats()
        assert st["compile"]["hits"] >= 1


class TestConcurrentServiceLevels:
    def test_mixed_priority_load_all_bit_identical(self):
        """20 threads x mixed priorities/tenants under an adaptive server:
        exactly-once, bit-identical, priority counters add up."""
        cfg = ServerConfig(max_batch=4, max_delay_ms=5.0, adaptive=True,
                           overload_shed=True, max_pending=256,
                           tenant_quota=128)
        results: dict[int, np.ndarray] = {}
        errs: list = []

        def client(uid: int) -> None:
            pri = ("high", "normal", "low")[uid % 3]
            try:
                fut = cfg_srv.submit(image(uid), "gaussian3", priority=pri,
                                     tenant=f"t{uid % 2}", slo_ms=1000.0)
                results[uid] = fut.result(120)
            except Exception as e:                       # noqa: BLE001
                errs.append(e)

        with ImageFilterServer(cfg) as cfg_srv:
            threads = [threading.Thread(target=client, args=(u,))
                       for u in range(20)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(150)
            st = cfg_srv.stats()
        assert not errs and len(results) == 20
        for uid, out in results.items():
            np.testing.assert_array_equal(
                out, np.asarray(apply_filter(image(uid), "gaussian3")))
        assert sum(st["served_priority"].values()) == st["served"] == 20
