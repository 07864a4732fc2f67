"""Micro-batch executor: flushed buckets -> the filter datapath
(DESIGN.md §10), with the failure-isolation and degradation machinery of
DESIGN.md §12.

One `MicroBatch` becomes one workload dispatch (DESIGN.md §14): the
bucket's requests hand off to their registered `Workload` class -- for
the default filter workload, one `apply_filter_batch` call where the
requests stack into an (N, H, W) batch that rides the §8 batch fold, runs
under the bucket's execution mode ('local' | 'sharded' | 'streamed', §9),
and splits back per request; for the infer workload, one batched
quantized forward pass (`repro.infer.serving`). Bit-exactness end to end is inherited, not
re-argued: the batch fold embeds each image's own zero halo and every
exec mode is bit-identical to local, so a request's output is the same
bytes no matter which coalesced batch, bucket, or exec mode served it
(asserted in tests/test_serve.py).

Two steady-state amortisations:

  * **per-bucket plan resolution** -- the full `PlanConfig` winner
    (dataflow and grid organization, DESIGN.md §11) for a
    (bucket, traced batch size) is resolved once via
    `repro.filters.resolve_filter_plan` and pinned explicitly on every
    dispatch, so the hot path never re-consults the tuning cache
    (local exec only: sharded/streamed trace shard-/tile-local shapes and
    must keep their own §9 cache keying). The memo is an LRU bounded at
    `plan_memo_max` entries (DESIGN.md §13): long-tail shape traffic
    recycles the coldest entry instead of growing memory without limit,
    and `stats()` reports `plan_hits` / `plan_misses` / `plan_evicts`;
  * **power-of-two batch rounding** -- the coalesced batch zero-pads up to
    the next power of two, bounding compiles per bucket at
    log2(max_batch)+1 instead of one per distinct occupancy. The
    `warmed`/`hits`/`misses` ledger keyed by `serve_key` is the
    warm-start compile cache's bookkeeping: `repro.serve.warmup`
    pre-populates it (and jax's underlying jit cache) so first-request
    latency is amortised away.

Failure handling (DESIGN.md §12), innermost to outermost:

  * **bisect-and-retry isolation** -- when a dispatch raises, the batch is
    split in half and each half re-dispatched; singletons that still raise
    get the exception on their own future. Coalescing is batch-invariant
    (bit-identity across occupancies, §10), so re-serving an innocent
    neighbor in a smaller batch returns the same bytes -- isolation costs
    at most 2·log2(N) extra dispatches per poisoned request, never
    correctness. Counted in `retries` (re-dispatches) / `isolated`
    (requests that kept the exception).
  * **per-bucket degraded fallback** -- a sharded/streamed bucket whose
    dispatch fails `degrade_after` consecutive times falls back to
    `exec='local'` (bit-identical by the §9 contract) for the rest of the
    server's life; fallback dispatches are counted per bucket in
    `degraded`. Successful scale-out dispatches reset the consecutive
    counter.
  * **leak-proof fulfilment** -- `run()` never raises and fulfils every
    future exactly once even when the datapath (or fulfilment itself)
    raises mid-bucket: unresolved futures inherit the error, so no future
    can hang and no admission slot can leak.

The deterministic chaos harness (`repro.runtime.fault`) probes
`SITE_EXECUTE` on every dispatch with the serve key, the exec mode
actually used, the executor's pool-member `name` (when set), and the
batch's request sequence numbers -- the hooks the §12/§13 tests and
`scripts/check.sh --smoke-fault` / `--smoke-slo` drive.

Pool integration (DESIGN.md §13): `name` tags the executor's probe keys
so chaos rules can target one pool member; `devices` additionally accepts
an explicit device-id tuple (the elastic pool's device-subset meshes,
`repro.distribute.mesh.filter_mesh`), and a local dispatch then runs on
the first device of that tuple, so one-chip pool members each use their
own chip; and `on_dispatch(key, mode, ok)` reports every dispatch outcome
to the owning `ExecutorPool`'s health tracker.

Telemetry (DESIGN.md §15): the ledger counters live in a
`repro.obs.MetricsRegistry` (labelled `member=` so pool members share
one registry without colliding); the historical attribute API
(`ex.hits`, `ex.retries`, ...) is preserved as properties reading the
registry. Always-on counters price the host <-> device traffic:
`serve_h2d_bytes_total{member}` (the stacked batch handed to the device,
pad images included, in the dtype it crosses in: 1 or 2 bytes a slot for
uint8 or uint16 pixels, 4 for the int32 batch of any other dtype),
`serve_d2h_bytes_total{member, container}` (the answers fetched back,
pad images included, in their pixel container) and
`serve_pixels_total{member}` (real pixels served). With a `trace=` recorder, every dispatch is a 'dispatch' span
(its `batch` id inherited from the server's 'batch' span, `member`,
`skey`, `warm`) around the workload's leaf phase spans (prep, upload,
launch, device_wait, fetch; `repro.obs.trace.PHASES`), emits
per-request 'dispatch' events (serve key, exec mode actually used,
traced batch size, resolved §11 plan tag, batch id), and every
fulfilment (a 'fulfil' leaf) or isolated failure its terminal event.
Tracing defaults off (the no-op recorder).
"""
from __future__ import annotations

import contextlib
import threading
from collections import OrderedDict
from typing import Callable, Sequence

import jax
import numpy as np

from repro.distribute.mesh import devices_by_id
from repro.filters.pipeline import resolve_filter_plan
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NOOP
from repro.runtime.fault import SITE_EXECUTE
from repro.runtime.fault import probe as fault_probe
from repro.serve.batcher import MicroBatch
from repro.serve.request import FilterRequest, bucket_key, serve_key
from repro.serve.workload import Workload, resolve_workloads
from repro.tuning import cache_generation

#: exec modes eligible for the per-bucket local fallback (§12)
SCALE_OUT_MODES = ("sharded", "streamed")


def next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1)).bit_length()


def warm_container(nbits: int, workload: str = "filter",
                   exec_mode: str = "local") -> str:
    """The pixel container a warm-up at `nbits` compiles for when none is
    named: a multiplier wider than 8 bits is asked for by pixels wider
    than 8 bits, which come in uint16 (DESIGN.md §5) and are served on
    exec='local'; everything else, and every workload but 'filter', in
    uint8."""
    wide = workload == "filter" and exec_mode == "local" and nbits > 8
    return "uint16" if wide else "uint8"


class BatchExecutor:
    """Stateless-per-request executor with the per-bucket plan memo."""

    def __init__(self, *, interpret: bool | None = None,
                 pad_pow2: bool = True,
                 devices: int | Sequence[int] | None = None,
                 tile: tuple[int, int] = (256, 256),
                 tile_batch: int = 8, degrade_after: int = 2,
                 plan_memo_max: int = 256, name: str = "",
                 on_dispatch: Callable[[str, str, bool], None] | None = None,
                 workloads: dict[str, Workload] | None = None,
                 metrics: MetricsRegistry | None = None,
                 trace=NOOP) -> None:
        self.interpret = interpret
        self.workloads = resolve_workloads(workloads)
        self.pad_pow2 = pad_pow2
        self.devices = (tuple(devices) if isinstance(devices, (list, tuple))
                        else devices)
        # where exec='local' dispatches run: a pool member's own first
        # device (§13), else the process default
        self._local_device = (devices_by_id(self.devices[:1])[0]
                              if isinstance(self.devices, tuple) else None)
        self.tile = tuple(tile)
        self.tile_batch = int(tile_batch)
        self.degrade_after = max(int(degrade_after), 1)
        self.plan_memo_max = max(int(plan_memo_max), 1)
        self.name = str(name)
        self.on_dispatch = on_dispatch
        self._lock = threading.Lock()
        self._plans: OrderedDict[tuple, dict] = OrderedDict()
        self._plans_gen = cache_generation()
        self.warmed: set[str] = set()
        # ------------------------------ §15 telemetry (registry-backed)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._trace = trace
        m = self.metrics
        self._c_hits = m.counter("serve_compile_hits_total")
        self._c_misses = m.counter("serve_compile_misses_total")
        self._c_plan_hits = m.counter("serve_plan_hits_total")
        self._c_plan_misses = m.counter("serve_plan_misses_total")
        self._c_plan_evicts = m.counter("serve_plan_evicts_total")
        self._c_retries = m.counter("serve_retries_total")
        self._c_isolated = m.counter("serve_isolated_total")
        self._c_degraded = m.counter("serve_degraded_total")
        self._c_h2d = m.counter("serve_h2d_bytes_total")
        self._c_d2h = m.counter("serve_d2h_bytes_total")
        self._c_pixels = m.counter("serve_pixels_total")
        # ------------------------------ §12 fault-tolerance state
        self.failures: dict[str, int] = {}   # bucket -> consecutive failures
        self._fallback: set[str] = set()     # buckets pinned to local exec

    # ------------------------------------------------ registry-backed ledger
    @property
    def hits(self) -> int:
        return self._c_hits.value(member=self.name)

    @property
    def misses(self) -> int:
        return self._c_misses.value(member=self.name)

    @property
    def plan_hits(self) -> int:
        return self._c_plan_hits.value(member=self.name)

    @property
    def plan_misses(self) -> int:
        return self._c_plan_misses.value(member=self.name)

    @property
    def plan_evicts(self) -> int:
        return self._c_plan_evicts.value(member=self.name)

    @property
    def retries(self) -> int:
        return self._c_retries.value(member=self.name)

    @property
    def isolated(self) -> int:
        return self._c_isolated.value(member=self.name)

    @property
    def degraded(self) -> dict[str, int]:
        """bucket -> §12 local-fallback dispatch count (this member's)."""
        return self._c_degraded.group_by("bucket", member=self.name)

    def count_transfer(self, h2d_bytes: int, pixels: int) -> None:
        """One dispatch's host -> device bytes and real pixels (§15)."""
        self._c_h2d.inc(h2d_bytes, member=self.name)
        self._c_pixels.inc(pixels, member=self.name)

    def count_fetch(self, d2h_bytes: int, container: str) -> None:
        """One dispatch's device -> host bytes, by pixel container (§15)."""
        self._c_d2h.inc(d2h_bytes, member=self.name, container=container)

    # -------------------------------------------------- per-bucket plan memo
    def _plan(self, filt: str, method: str, n: int, h: int, w: int) -> dict:
        """Explicit plan fields for a local-exec (n, h, w) dispatch of
        `filt` -- the full `PlanConfig` (dataflow and grid organization,
        DESIGN.md §11) resolved once per (bucket, traced batch size),
        pinned on every later call (the §10 hot-path
        memoisation: all-explicit fields take `resolve_plan`'s fast path).
        The memo follows the tuning cache's generation so an
        `invalidate_cache()` (an autotune store under a running server)
        drops stale pinned winners instead of serving them for the
        server's lifetime, and is LRU-bounded at `plan_memo_max` entries
        so long-tail shape traffic cannot grow it without limit
        (DESIGN.md §13)."""
        memo_key = (filt, method, n, h, w)
        with self._lock:
            gen = cache_generation()
            if gen != self._plans_gen:
                self._plans.clear()
                self._plans_gen = gen
            plan = self._plans.get(memo_key)
            if plan is not None:
                self._c_plan_hits.inc(member=self.name)
                self._plans.move_to_end(memo_key)
                return plan
            self._c_plan_misses.inc(member=self.name)
        cfg = resolve_filter_plan(filt, n, h, w, method=method,
                                  interpret=self.interpret)
        plan = {"separable": cfg.dataflow != "direct",
                "fused": cfg.dataflow == "fused",
                "block_rows": cfg.block_rows,
                "block_cols": cfg.block_cols,
                "batch_fold": cfg.batch_fold}
        with self._lock:
            self._plans[memo_key] = plan
            self._plans.move_to_end(memo_key)
            while len(self._plans) > self.plan_memo_max:
                self._plans.popitem(last=False)
                self._c_plan_evicts.inc(member=self.name)
        return plan

    def _exec_kw(self, exec_mode: str, filt: str, method: str, n: int,
                 h: int, w: int) -> dict:
        """Complete per-dispatch kwargs (the local plan pins every plan
        field; scale-out modes resolve their own)."""
        if exec_mode == "local":
            return dict(self._plan(filt, method, n, h, w))
        if exec_mode == "sharded":
            return {"exec": "sharded", "devices": self.devices}
        if exec_mode == "streamed":
            # tiles never exceed the bucket's image -- tiny buckets stream
            # as one tile instead of erroring on an oversized plan
            th, tw = min(self.tile[0], h), min(self.tile[1], w)
            return {"exec": "streamed", "tile": (th, tw),
                    "tile_batch": self.tile_batch}
        raise ValueError(f"unknown exec mode {exec_mode!r}")

    def _plan_tag(self, mode: str, r0: FilterRequest, traced_n: int) -> str:
        """Compact spelling of the dispatch's resolved execution plan for
        the §15 'dispatch' events: the §11 PlanConfig for a local filter
        dispatch, the exec mode (+ workload) otherwise. Only computed when
        tracing is on; the memo makes it a plan-memo hit."""
        if mode == "local" and r0.workload == "filter":
            h, w = r0.img.shape
            p = self._plan(r0.filt, r0.method, traced_n, h, w)
            df = ("fused" if p["fused"]
                  else "two_pass" if p["separable"] else "direct")
            tag = f"{df}/br{p['block_rows']}xbc{p['block_cols']}"
            return tag + ("/fold" if p["batch_fold"] else "")
        return f"{mode}/{r0.workload}"

    # ------------------------------------------------------------- execution
    def _placed(self, mode: str):
        """Context that runs a local dispatch on this member's device."""
        if mode == "local" and self._local_device is not None:
            return jax.default_device(self._local_device)
        return contextlib.nullcontext()

    def execute(self, key: str, requests: tuple[FilterRequest, ...], *,
                exec_override: str | None = None) -> list[np.ndarray]:
        """One dispatch of a coalesced bucket slice, no retry; returns one
        output per request. `exec_override` is the §12 fallback hook."""
        r0 = requests[0]
        n = len(requests)
        traced_n = next_pow2(n) if self.pad_pow2 else n
        skey = serve_key(key, traced_n)
        with self._lock:
            warm = skey in self.warmed
            if not warm:
                self.warmed.add(skey)
        if warm:
            self._c_hits.inc(member=self.name)
        else:
            self._c_misses.inc(member=self.name)
        mode = r0.exec if exec_override is None else exec_override
        tag = f"|member={self.name}" if self.name else ""
        fault_probe(SITE_EXECUTE, key=f"{skey}|exec={mode}{tag}",
                    seqs=tuple(r.seq for r in requests))
        wl = self.workloads.get(r0.workload)
        if wl is None:
            raise KeyError(f"no workload {r0.workload!r} registered "
                           f"(have: {tuple(self.workloads)})")
        with self._trace.span("dispatch", member=self.name, skey=skey,
                              warm=warm) as span:
            if self._trace.enabled:
                plan = self._plan_tag(mode, r0, traced_n)
                for r in requests:
                    self._trace.event("dispatch", seq=r.seq, bucket=key,
                                      skey=skey, exec=mode, n=n,
                                      traced_n=traced_n, plan=plan,
                                      member=self.name,
                                      workload=r0.workload,
                                      batch=span.get("batch"))
            with self._placed(mode):
                return wl.execute(self, requests, traced_n, mode)

    def _report(self, key: str, mode: str, ok: bool) -> None:
        """Tell the owning pool (if any) how one dispatch went -- the §13
        health feed. Reporter faults must never corrupt fulfilment."""
        if self.on_dispatch is not None:
            try:
                self.on_dispatch(key, mode, ok)
            except Exception:                              # noqa: BLE001
                pass

    def _dispatch(self, key: str, requests: tuple[FilterRequest, ...]
                  ) -> list[np.ndarray]:
        """`execute` under the per-bucket degraded-exec ladder (§12): a
        scale-out bucket that failed `degrade_after` consecutive dispatches
        is pinned to the bit-identical local path. Every dispatch outcome
        (with the exec mode actually used) feeds `on_dispatch` (§13)."""
        mode = requests[0].exec
        scale_out = mode in SCALE_OUT_MODES
        if scale_out and key in self._fallback:
            outs = self.execute(key, requests, exec_override="local")
            self._report(key, "local", True)
            self._c_degraded.inc(member=self.name, bucket=key)
            return outs
        try:
            outs = self.execute(key, requests)
        except BaseException:                              # noqa: BLE001
            self._report(key, mode, False)
            if scale_out:
                with self._lock:
                    nfail = self.failures.get(key, 0) + 1
                    self.failures[key] = nfail
                    if nfail >= self.degrade_after:
                        self._fallback.add(key)
                if key in self._fallback:
                    outs = self.execute(key, requests, exec_override="local")
                    self._report(key, "local", True)
                    self._c_degraded.inc(member=self.name, bucket=key)
                    return outs
            raise
        self._report(key, mode, True)
        if scale_out:
            with self._lock:
                self.failures[key] = 0
        return outs

    def _fulfil(self, key: str, requests: tuple[FilterRequest, ...], *,
                retry: bool = False) -> None:
        """Dispatch + fulfil with bisection isolation: a failing batch
        splits in half and each half re-dispatches, so only requests that
        fail *alone* keep the exception (§12). Byte-safe: outputs are
        batch-invariant (§10), so a re-served neighbor gets the same bits."""
        if retry:
            self._c_retries.inc(member=self.name)
        try:
            outs = self._dispatch(key, requests)
        except BaseException as err:                       # noqa: BLE001
            if len(requests) == 1:
                self._c_isolated.inc(member=self.name)
                if not requests[0].future.done():
                    requests[0].future.set_exception(err)
                    if self._trace.enabled:
                        self._trace.event("fail", seq=requests[0].seq,
                                          bucket=key, cause="isolated",
                                          error=repr(err))
                return
            mid = len(requests) // 2
            self._fulfil(key, requests[:mid], retry=True)
            self._fulfil(key, requests[mid:], retry=True)
            return
        with self._trace.span("fulfil"):
            for req, out in zip(requests, outs):
                if not req.future.done():
                    req.future.set_result(out)
                    if self._trace.enabled:
                        self._trace.event("fulfil", seq=req.seq, bucket=key)

    def run(self, batch: MicroBatch) -> None:
        """Execute and fulfil -- every future resolves exactly once, to its
        own request's output or to its own (isolated) failure. Never
        raises: any error escaping the isolation machinery itself lands on
        the still-unresolved futures, so none can hang (§12)."""
        try:
            self._fulfil(batch.key, batch.requests)
        except BaseException as err:                       # noqa: BLE001
            for req in batch.requests:
                if not req.future.done():
                    req.future.set_exception(err)
                    if self._trace.enabled:
                        self._trace.event("fail", seq=req.seq,
                                          bucket=batch.key,
                                          cause="executor", error=repr(err))

    @property
    def degraded_mode(self) -> bool:
        """True once any bucket has been pinned to the local fallback."""
        return bool(self._fallback)

    def fault_stats(self) -> dict:
        """Snapshot of the §12 counters (the server's stats() source)."""
        with self._lock:
            failures = dict(self.failures)
        return {"retries": self.retries, "isolated": self.isolated,
                "degraded": self.degraded,
                "dispatch_failures": failures}

    def stats(self) -> dict:
        """Full executor snapshot: the warm compile ledger, the §13
        LRU plan-memo counters, and the §12 fault counters."""
        with self._lock:
            warmed = len(self.warmed)
            plan_size = len(self._plans)
        snap = {"warmed": warmed, "hits": self.hits,
                "misses": self.misses,
                "plan_memo": {"size": plan_size,
                              "max": self.plan_memo_max,
                              "hits": self.plan_hits,
                              "misses": self.plan_misses,
                              "evicts": self.plan_evicts}}
        snap.update(self.fault_stats())
        return snap

    # ---------------------------------------------------------------- warmup
    def warm(self, shape: tuple[int, int], filt: str, *,
             method: str = "refmlm", exec_mode: str = "local", nbits: int = 8, n: int = 1,
             priority: str = "normal", workload: str = "filter") -> str:
        """Pre-compile one (bucket, batch size) point with a zero dummy
        batch; returns the serve_key it warmed. `priority` only names the
        warmed ledger bucket (classes never coalesce, §13) -- the compiled
        executable underneath is priority-blind and shared. `workload`
        selects the §14 workload class doing the compiling (filter by
        default; `filt` then names that workload's target, e.g. an infer
        model). The pixel container is the one `nbits` is for
        (`warm_container`, DESIGN.md §5)."""
        h, w = shape
        traced_n = next_pow2(n) if self.pad_pow2 else n
        container = warm_container(nbits, workload, exec_mode)
        key = bucket_key(filt, method, exec_mode, nbits, h, w,
                         priority, workload, container)
        with self._placed(exec_mode):
            self.workloads[workload].warm(
                self, (h, w), filt, method=method, exec_mode=exec_mode, nbits=nbits, traced_n=traced_n,
                container=container)
        skey = serve_key(key, traced_n)
        with self._lock:
            self.warmed.add(skey)
        return skey


__all__ = ["BatchExecutor", "SCALE_OUT_MODES", "next_pow2", "warm_container"]
