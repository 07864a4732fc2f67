"""Pluggable serving workload classes (DESIGN.md §14).

A `Workload` is everything the serving machinery does NOT need to know
about the work it coalesces: payload validation and its pixel container,
the actual device dispatch of a flushed bucket, deploy-time warmup, and
the cost-model hook the adaptive controller prices flushes with.
Everything else -- admission, weighted quotas, shape-bucketed batching,
priorities, SLO-adaptive flush policy, bisection fault isolation, the
elastic pool -- operates on `FilterRequest`/`MicroBatch` alone and
carries over unchanged (§10-§13).

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
from repro.filters.pipeline import (
    CONTAINERS,
    apply_filter_batch,
    batch_dtype,
    check_method_nbits,
    container_of,
    filter_nbits,
)
from repro.serve.request import FilterRequest, request_weight


class Workload:
    """One serving workload class. Subclasses define the five hooks; the
    server, executor and controller call them through the `workloads`
    registry keyed by `FilterRequest.workload`."""

    name = "base"

    def validate(self, payload, *, target: str, method: str, exec_mode: str,
                 nbits: int) -> np.ndarray:
        """Client-thread validation: raise on a bad request, return the
        canonical 2-D payload array the request will carry."""
        raise NotImplementedError

    def weight(self, arr: np.ndarray) -> int:
        """Weighted admission slots this payload occupies (§13)."""
        return request_weight(*arr.shape[:2])

    def container(self, arr: np.ndarray) -> str:
        """The pixel container the validated payload is answered in
        (`FilterRequest.container`, part of its bucket key)."""
        return "uint8"

    def execute(self, executor, requests: tuple[FilterRequest, ...],
                traced_n: int, exec_mode: str) -> list[np.ndarray]:
        """One dispatch of a coalesced bucket slice on `executor`'s
        resources; one output per request, no retry (the §12 ladder wraps
        this)."""
        raise NotImplementedError

    def warm(self, executor, shape: tuple[int, int], target: str, *,
             method: str, exec_mode: str, nbits: int, traced_n: int,
             container: str = "uint8") -> None:
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
    executor's §11 plan memo, routed by the §9 exec modes. A uint16
    payload is answered in uint16 (DESIGN.md §5) on exec='local' only, by
    a method that has the widths its filter needs there; every other
    payload keeps the uint8 contract."""

    name = "filter"

    def validate(self, payload, *, target: str, method: str, exec_mode: str,
                 nbits: int) -> np.ndarray:
        get_filter(target)                   # unknown names fail fast
        arr = np.asarray(payload)
        if arr.ndim == 3 and arr.shape[-1] == 1:
            arr = arr[..., 0]
        if arr.ndim != 2:
            raise ValueError(f"expected one (H, W) image per request, got "
                             f"shape {arr.shape}")
        container = container_of(arr)
        if container != "uint8":
            if exec_mode != "local":
                raise ValueError(f"exec={exec_mode!r} answers uint8 pixels "
                                 f"only; {container} pixels take "
                                 f"exec='local'")
            check_method_nbits(method, filter_nbits(get_filter(target),
                                                    nbits, container))
        return arr

    def container(self, arr: np.ndarray) -> str:
        return container_of(arr)

    def execute(self, executor, requests: tuple[FilterRequest, ...],
                traced_n: int, exec_mode: str) -> list[np.ndarray]:
        r0 = requests[0]
        h, w = r0.img.shape
        kw = executor._exec_kw(exec_mode, r0.filt, r0.method, traced_n, h, w)
        imgs = [r.img for r in requests]
        # the batch as uploaded, pad images included, in its wire dtype
        executor.count_transfer(
            traced_n * h * w * batch_dtype(imgs).itemsize,
            len(requests) * h * w)
        outs = apply_filter_batch(
            imgs, r0.filt, pad_to=traced_n,
            method=r0.method, nbits=r0.nbits,
            interpret=executor.interpret, **kw)
        # the fetched batch, pad images included, in the answers' dtype
        executor.count_fetch(traced_n * h * w * outs[0].dtype.itemsize,
                             r0.container)
        return outs

    def warm(self, executor, shape: tuple[int, int], target: str, *,
             method: str, exec_mode: str, nbits: int, traced_n: int,
             container: str = "uint8") -> None:
        h, w = shape
        kw = executor._exec_kw(exec_mode, target, method, traced_n, h, w)
        zeros = np.zeros((h, w), CONTAINERS[container])
        apply_filter_batch([zeros] * traced_n, target, method=method,
                           nbits=nbits, interpret=executor.interpret, **kw)

    def model_bound(self, req: FilterRequest, n: int, *,
                    backend: str | None = None) -> float | None:
        """Roofline lower bound of the bucket's resolved §11 plan."""
        from repro.filters.pipeline import resolve_filter_plan
        from repro.roofline.conv_model import plan_cost
        h, w = req.img.shape
        spec = get_filter(req.filt)
        plan = resolve_filter_plan(spec, n, h, w, method=req.method)
        kh, kw = ((len(spec.sep_col), len(spec.sep_row))
                  if plan.dataflow == "fused" else spec.ksize)
        cost = plan_cost(plan.dataflow, n, h, w, kh, kw,
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
