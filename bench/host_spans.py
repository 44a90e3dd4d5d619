"""Mean host time of one of the scheduler's per-flush profiler spans
inside the traced window.

The scheduler opens one ``jax.profiler.TraceAnnotation`` a flush for
each host stage: ``serve.assemble`` (queue waits recorded, buffers
leased and filled, executable fetched), ``serve.dispatch`` (read by
``bench.chip_trace.dispatch_ms``) and ``serve.settle`` (results on the
host to the flush's last future settled).  The trace is read once per
run through ``bench.chip_trace``; a span is clipped to the window, and
a program that opens no such span (one older than it) gives ``None``.
"""
from __future__ import annotations

from bench import chip_trace


def span_ms(ctx, name: str):
    """Mean milliseconds of the ``name`` spans inside the window;
    ``None`` where there is none."""
    ev = chip_trace._events(ctx)
    if ev is None:
        return None
    host = ev[1]
    w0, w1 = chip_trace._window(host)
    spans = [min(e, w1) - max(s, w0) for n, s, e in host if n == name]
    spans = [d for d in spans if d > 0]
    if not spans:
        return None
    return sum(spans) / len(spans) / 1e6
