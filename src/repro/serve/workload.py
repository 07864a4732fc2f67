"""Pluggable serving workload classes (DESIGN.md §14).

A `Workload` is everything the serving machinery does NOT need to know
about the work it coalesces: payload validation, the actual device
dispatch of a flushed bucket, deploy-time warmup, and the cost-model hook
the adaptive controller prices flushes with. Everything else -- admission,
weighted quotas, shape-bucketed batching, priorities, SLO-adaptive flush
policy, bisection fault isolation, the elastic pool -- operates on
`FilterRequest`/`MicroBatch` alone and carries over unchanged (§10-§13).

Two instances ship:

  * `FilterWorkload` ('filter') -- the original image-filter path:
    `apply_filter_batch` under the §11 plan memo and the §9 exec modes;
  * `repro.infer.serving.InferWorkload` ('infer') -- quantized network
    inference on the approximate-multiplier stack (§14), with its own
    jit-cached forward per (model, method, traced batch size).

The workload name rides the `bucket_key` (request.py), so distinct
workload classes can never coalesce into one batch even when every other
routing field agrees. Both dispatch paths are batch-invariant and
deterministic, so the serving guarantee -- served bytes == direct-call
bytes, any flush size -- holds per workload.
"""
from __future__ import annotations

import numpy as np

from repro.filters.bank import get_filter
from repro.filters.conv import MULT_IMPLS
from repro.filters.pipeline import apply_filter_batch
from repro.serve.request import FilterRequest, request_weight


class Workload:
    """One serving workload class. Subclasses define the five hooks; the
    server, executor and controller call them through the `workloads`
    registry keyed by `FilterRequest.workload`."""

    name = "base"

    def validate(self, payload, *, target: str, method: str, mult_impl: str,
                 exec_mode: str, nbits: int) -> np.ndarray:
        """Client-thread validation: raise on a bad request, return the
        canonical 2-D payload array the request will carry."""
        raise NotImplementedError

    def weight(self, arr: np.ndarray) -> int:
        """Weighted admission slots this payload occupies (§13)."""
        return request_weight(*arr.shape[:2])

    def execute(self, executor, requests: tuple[FilterRequest, ...],
                traced_n: int, exec_mode: str) -> list[np.ndarray]:
        """One dispatch of a coalesced bucket slice on `executor`'s
        resources; one output per request, no retry (the §12 ladder wraps
        this)."""
        raise NotImplementedError

    def warm(self, executor, shape: tuple[int, int], target: str, *,
             method: str, mult_impl: str, exec_mode: str, nbits: int,
             traced_n: int) -> None:
        """Compile one (bucket, traced batch size) point with dummy data."""
        raise NotImplementedError

    def model_bound(self, req: FilterRequest, n: int, *,
                    backend: str | None = None) -> float | None:
        """Analytic lower bound (seconds) of one `n`-sized dispatch, for
        the §13 controller's cold-start prediction. None = no model (the
        controller falls back to its observation floor)."""
        return None


class FilterWorkload(Workload):
    """The image-filter path: one micro-batch becomes one
    `apply_filter_batch` call riding the §8 batch fold, planned by the
    executor's §11 plan memo, routed by the §9 exec modes."""

    name = "filter"

    def validate(self, payload, *, target: str, method: str, mult_impl: str,
                 exec_mode: str, nbits: int) -> np.ndarray:
        if mult_impl not in MULT_IMPLS:
            raise ValueError(f"mult_impl must be one of {MULT_IMPLS}, got "
                             f"{mult_impl!r}")
        get_filter(target)                   # unknown names fail fast
        arr = np.asarray(payload)
        if arr.ndim == 3 and arr.shape[-1] == 1:
            arr = arr[..., 0]
        if arr.ndim != 2:
            raise ValueError(f"expected one (H, W) image per request, got "
                             f"shape {arr.shape}")
        return arr

    def execute(self, executor, requests: tuple[FilterRequest, ...],
                traced_n: int, exec_mode: str) -> list[np.ndarray]:
        r0 = requests[0]
        h, w = r0.img.shape
        kw = executor._exec_kw(exec_mode, r0.filt, r0.method, r0.mult_impl,
                               traced_n, h, w)
        return apply_filter_batch(
            [r.img for r in requests], r0.filt, pad_to=traced_n,
            method=r0.method, nbits=r0.nbits,
            interpret=executor.interpret, **kw)

    def warm(self, executor, shape: tuple[int, int], target: str, *,
             method: str, mult_impl: str, exec_mode: str, nbits: int,
             traced_n: int) -> None:
        h, w = shape
        kw = executor._exec_kw(exec_mode, target, method, mult_impl,
                               traced_n, h, w)
        apply_filter_batch([np.zeros((h, w), np.int32)] * traced_n, target,
                           method=method, nbits=nbits,
                           interpret=executor.interpret, **kw)

    def model_bound(self, req: FilterRequest, n: int, *,
                    backend: str | None = None) -> float | None:
        """Roofline lower bound of the bucket's resolved §11 plan."""
        from repro.filters.pipeline import resolve_filter_plan
        from repro.roofline.conv_model import plan_cost
        h, w = req.img.shape
        spec = get_filter(req.filt)
        plan = resolve_filter_plan(spec, n, h, w, method=req.method,
                                   mult_impl=req.mult_impl)
        kh, kw = ((len(spec.sep_col), len(spec.sep_row))
                  if plan.dataflow == "fused" else spec.ksize)
        cost = plan_cost(plan.dataflow, plan.mult_impl, n, h, w, kh, kw,
                         block_rows=plan.block_rows,
                         block_cols=plan.block_cols,
                         batch_fold=bool(plan.batch_fold),
                         backend=backend)
        return cost.lower_bound_s


def resolve_workloads(extra: dict[str, Workload] | None = None
                      ) -> dict[str, Workload]:
    """The serving registry: the built-in filter workload plus any extra
    classes (e.g. `InferWorkload`). 'filter' is always present so the
    default submit path never misses."""
    registry: dict[str, Workload] = {"filter": FilterWorkload()}
    registry.update(extra or {})
    return registry


__all__ = ["FilterWorkload", "Workload", "resolve_workloads"]
