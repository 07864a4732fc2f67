"""`repro.obs` -- unified serving telemetry (DESIGN.md §15).

Two cooperating pieces, zero-cost when off:

  metrics.py  -- `MetricsRegistry`: thread-safe bounded
                 counters/gauges/histograms with label sets. The serving
                 layer's one source of operational truth: every counter
                 that used to live in an ad-hoc dict (server, admission
                 gate, batcher outcomes, executor ledgers, controller,
                 pool) now lives here, and `server.stats()` reads them
                 under ONE lock -- a consistent snapshot by construction.
  trace.py    -- per-request spans (`submit -> admit -> enqueue -> flush
                 -> dispatch -> fulfil|shed|fail`) plus fault/shard/tile/
                 infer events on the same stream; the serve worker's
                 phase spans (`PHASES`: idle, collect, prep, upload,
                 launch, device_wait, fetch, fulfil), each also a
                 `jax.profiler.TraceAnnotation` on the device trace's
                 clock; JSONL and Perfetto (Chrome trace-event) export;
                 `NOOP` when off.

Operator CLI: `python -m repro.obs.snapshot trace.jsonl [--chrome out]`.

Wiring: `ServerConfig(trace=...)` on `ImageFilterServer`
(DESIGN.md §10/§15); standalone components accept `metrics=`/`trace=`
and default to private registries / the no-op recorder.
"""
from __future__ import annotations

from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import (
    NOOP,
    PHASES,
    STAGES,
    TERMINALS,
    NoopRecorder,
    TraceRecorder,
    chrome_trace,
    emit,
    resolve_trace,
    span,
    trace_scope,
    tracing,
)

__all__ = [
    "NOOP",
    "PHASES",
    "STAGES",
    "TERMINALS",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NoopRecorder",
    "TraceRecorder",
    "chrome_trace",
    "emit",
    "resolve_trace",
    "span",
    "trace_scope",
    "tracing",
]
