"""Per-chip busy time and the scheduler's dispatch spans, from the
profiler's trace of the window.

``bench.trace_reduce`` keeps only the mean busy time over the chips.
The readers here read the trace's events again with
``trace_reduce.read_events``, once per run (kept in the readers' shared
``ctx``), and count only what lies inside the ``bench.window``
annotation:

- a chip's busy time is the union of its ``XLA Ops`` intervals;
- each flush's dispatch is a ``serve.dispatch`` host span that the
  scheduler opens around the asynchronous dispatch call.  A program
  that opens none (one older than the span) gives ``None``.
"""
from __future__ import annotations

from bench import stats, trace_reduce

DISPATCH = "serve.dispatch"


def _events(ctx):
    path = ctx["outcome"].trace_path
    if not path:
        return None
    cache = ctx.setdefault(__name__, {})
    if path not in cache:
        cache[path] = trace_reduce.read_events(path)
    return cache[path]


def _window(host):
    win = [h for h in host if h[0] == trace_reduce.WINDOW]
    if not win:
        raise ValueError("the trace has no bench.window annotation")
    return win[0][1], win[0][2]


def busy_s(devices: dict, host: list) -> dict:
    """Seconds each chip of the trace was busy inside the window."""
    w0, w1 = _window(host)
    out = {}
    for dev, ops in devices.items():
        ivs = [(max(s, w0), min(e, w1)) for _, s, e, _ in ops
               if min(e, w1) > max(s, w0)]
        out[dev] = stats.union_length(ivs) / 1e9
    return out


def busy_imbalance(ctx):
    """The busiest chip's busy time over the mean of the cell's chips
    (a chip with no op in the window counts as idle); ``None`` where no
    chip was busy."""
    ev = _events(ctx)
    if ev is None:
        return None
    busy = list(busy_s(*ev).values())
    busy += [0.0] * (ctx["n_devices"] - len(busy))
    if not busy or sum(busy) <= 0:
        return None
    return max(busy) / (sum(busy) / len(busy))


def dispatch_ms(ctx):
    """Host time per ``serve.dispatch`` span inside the window; ``None``
    where there is none."""
    ev = _events(ctx)
    if ev is None:
        return None
    host = ev[1]
    w0, w1 = _window(host)
    spans = [min(e, w1) - max(s, w0) for name, s, e in host
             if name == DISPATCH]
    spans = [d for d in spans if d > 0]
    if not spans:
        return None
    return sum(spans) / len(spans) / 1e6
