"""What a driver hands back to the harness, and the helpers drivers share."""
from __future__ import annotations

import contextlib
import dataclasses
import shutil
import sys
import tempfile
from typing import Optional

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class RunContext:
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_start: float          # perf_counter when the process started
    # Set-up a driver may keep for the next run in the same process
    # (``bench/calibrate.py``); the harness's own runs pass None.
    keep: Optional[dict] = None

    def log(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Outcome:
    setup_s: float
    end_to_end: dict            # metric name -> value
    attempted: int
    failed: int
    sample: list                # groups of LPs and the program's answers
    device: dict                # bench.device.describe, read after the window
    counters: dict = dataclasses.field(default_factory=dict)
    trace_path: Optional[str] = None
    generator: dict = dataclasses.field(default_factory=dict)


class CompileCounter:
    """Counts the executables JAX builds (compiled or read from the
    persistent cache) while it is installed."""

    def __init__(self):
        self.n = 0
        self.names = []

    def _on(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            self.n += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


@contextlib.contextmanager
def profiled(enabled: bool, out: dict):
    """Run the block under ``jax.profiler`` when ``enabled``; ``out`` gets
    ``trace_path`` (the ``.xplane.pb``) and ``trace_dir``; a
    ``bench.window`` annotation brackets the block in the trace."""
    if not enabled:
        yield
        return
    import jax
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    out["trace_dir"] = trace_dir
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # Python tracing would slow the host path
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield
    finally:
        jax.profiler.stop_trace()
    import glob
    found = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not found:
        raise RuntimeError("the profiler wrote no .xplane.pb")
    out["trace_path"] = found[0]


def remove_trace(outcome: Outcome) -> None:
    if outcome.trace_path:
        root = outcome.trace_path.split("/plugins/")[0]
        shutil.rmtree(root, ignore_errors=True)
