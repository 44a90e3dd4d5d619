"""repro.obs — dependency-free observability for the serving stack.

End-to-end tracing, flush timelines, and a flight recorder: the
instrument that turns "device utilization near 100%" from a claim into
a measurement.  Everything here is stdlib-only (the no-new-deps rule)
and built so the *disabled* path costs nothing but a counter bump —
serving with tracing off must stay within noise of not having this
package at all.

Layers::

    trace     TraceContext (128-bit trace id), typed Spans, the
              SpanBuffer ring and the Tracer front door
    export    Chrome trace_event JSON (Perfetto-loadable), the span
              chain checker, and the measured device-idle fraction
    recorder  FlightRecorder: ring + scheduler-state snapshots dumped
              to a bounded JSON spool on errors / SLO violations /
              p99-threshold flushes
    log       stdlib-logging JSON formatter with trace_id/span_id/
              tenant/bucket injected from the active context
    profiler  opt-in jax.profiler start_trace/stop_trace session so
              device traces line up with host spans

The span taxonomy (see README "Observability" for the full table):
``rpc.handle`` -> ``admit`` -> ``request`` -> ``queue.wait`` ->
``flush.assemble`` -> ``flush.dispatch`` -> ``device.solve`` (one per
launch group) -> ``flush.scatter``.
"""
from repro.obs.export import (check_span_chains, device_idle,
                              to_chrome_trace)
from repro.obs.log import JsonFormatter, setup_logging
from repro.obs.recorder import FlightRecorder
from repro.obs.trace import (NOOP_TRACER, TRACE_HEADER, Span, SpanBuffer,
                             TraceContext, Tracer, current_context,
                             new_trace_context, parse_trace_header,
                             use_context)

__all__ = [
    "FlightRecorder", "JsonFormatter", "NOOP_TRACER", "Span",
    "SpanBuffer", "TRACE_HEADER", "TraceContext", "Tracer",
    "check_span_chains", "current_context", "device_idle",
    "new_trace_context", "parse_trace_header", "setup_logging",
    "to_chrome_trace", "use_context",
]
