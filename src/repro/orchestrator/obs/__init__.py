"""Observability layer: tick-clocked tracing + metrics for the serving
fleet.

* :mod:`repro.orchestrator.obs.metrics` -- per-pod :class:`MetricsRegistry`
  (counters / gauges / fixed-bucket histograms) with deterministic
  snapshots and fleet-level aggregation.
* :mod:`repro.orchestrator.obs.tracing` -- per-request lifecycle span
  events in bounded ring buffers, exportable to Chrome trace-event JSON
  (Perfetto-openable via ``serve --trace out.json``).
* :mod:`repro.orchestrator.obs.report` -- TTFT / inter-token-latency
  decomposition derived from spans, plus the span-log -> registry
  recompute used to check bitwise reproducibility.

Lifecycle spans stay on ticks so a replayed trace gives the same bytes.
Seconds come from the JAX profiler instead: ``tracing.span`` marks the
host work of each step as ``repro.*`` annotations on the clock of the
device's ops, recorded only while a profiler session runs
(``jax.profiler.start_trace(dir)`` ... ``stop_trace()`` around the serving
loop, then TensorBoard's profile plugin, Perfetto, or
``jax.profiler.ProfileData.from_file`` on the ``.xplane.pb`` it writes):

* ``repro.step`` (``tick``): one ``ContinuousScheduler.step``; inside it
  ``repro.admit`` (``rid``, ``queued_ticks``) per admission decision,
  ``repro.prefill`` (``rid``, ``positions``, ``bucket``, ``prefix_hit``)
  per ``SlotEngine.start`` with ``.dispatch`` (host-to-device copies and
  the enqueue), ``.wait`` (``block_until_ready``) and ``.insert`` (pool
  bookkeeping and the scatter into the pool), ``repro.decode``
  (``active``, ``chunk``) per ``SlotEngine.tick`` with ``.alloc``
  (alloc-on-write), ``.dispatch``, ``.wait``, ``.readback`` (device to
  host) and ``.walk`` (``tokens``: the host's walk over the chunk),
  ``repro.observe`` (``requests``) and ``repro.write_state``;
* ``repro.compile`` (``step``, ``hit``): a serve step looked up in, or
  compiled into, the Container's CompileCache.

On the device side every model call of a serve step runs under
``jax.named_scope("prefill")`` or ``("decode")``, and the Pallas paged
attention kernel is named ``paged_attention``.
"""

from repro.orchestrator.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    merge_snapshots,
    snapshot_count,
    snapshot_exemplar,
    snapshot_percentile,
    snapshot_total,
)
from repro.orchestrator.obs.report import (
    ITL_HIST,
    TICK_HIST,
    completion_snapshot,
    decomposition,
    itl_milliticks,
    observe_completion,
    recompute_registry,
    request_lifecycles,
)
from repro.orchestrator.obs.tracing import (
    SPAN_KINDS,
    SPAN_TRANSITIONS,
    TERMINAL_SPANS,
    SpanEvent,
    TraceBuffer,
    dump_span_log,
    export_chrome,
    load_span_log,
    span,
    validate_chrome_trace,
    validate_fleet_closure,
    validate_span_log,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "merge_snapshots", "snapshot_count", "snapshot_percentile",
    "snapshot_total",
    "TICK_HIST", "ITL_HIST", "completion_snapshot", "decomposition",
    "itl_milliticks", "observe_completion", "recompute_registry",
    "request_lifecycles", "snapshot_exemplar",
    "SPAN_KINDS", "SPAN_TRANSITIONS", "TERMINAL_SPANS", "SpanEvent",
    "TraceBuffer", "dump_span_log", "export_chrome", "load_span_log", "span",
    "validate_chrome_trace", "validate_fleet_closure", "validate_span_log",
]
