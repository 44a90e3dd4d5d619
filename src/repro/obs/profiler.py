"""Opt-in jax.profiler session that lines device traces up with host
spans: :class:`ProfileSession` brackets a whole run
(``--jax-profile-dir``) with ``jax.profiler.start_trace`` /
``stop_trace``.  The scheduler and sharding layers label dispatches
with ``jax.profiler.TraceAnnotation`` named like their host spans.

Profiler failures raise: a run asked for a trace must not exit 0
without one.

Caveat (documented in the README): on CPU backends the device
"profile" is host threads running compiled XLA code — annotations
still nest correctly, but there is no hardware timeline to align
against; treat CPU profiles as structural, not quantitative.
"""
from __future__ import annotations

from typing import Any, Optional

import jax.profiler as _jax_profiler


class ProfileSession:
    """Start/stop a jax profiler trace around a run.

    ``start()`` is a no-op (returning False) without a ``log_dir`` or
    when already started; ``stop()`` tolerates never-started and
    double-stop so shutdown paths can call it unconditionally.  Errors
    from the profiler itself propagate.
    """

    def __init__(self, log_dir: Optional[str]):
        self.log_dir = log_dir
        self.active = False

    def start(self) -> bool:
        if not self.log_dir or self.active:
            return False
        _jax_profiler.start_trace(self.log_dir)
        self.active = True
        return True

    def stop(self) -> bool:
        if not self.active:
            return False
        self.active = False
        _jax_profiler.stop_trace()
        return True

    def __enter__(self) -> "ProfileSession":
        self.start()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.stop()
