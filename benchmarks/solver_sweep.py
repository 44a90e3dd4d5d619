"""SolverSpec sweep: one batch, every backend, one JSON row each.

The unified front end makes "same problem, every backend, bit-for-bit
comparable" a one-liner, which is exactly what a perf trajectory needs:
each run times the identical batch through the full spec sweep and
emits machine-readable JSON rows (alongside the harness CSV line) that
later sessions can diff.
"""
from __future__ import annotations

import json

import jax

from benchmarks.common import emit, time_fn
from repro.core import random_feasible_lp
from repro.solver import SolverSpec


def sweep_specs(full: bool = False):
    """The canonical sweep: every backend, plus rgb tile/chunk tuning
    points when --full."""
    specs = [
        ("naive", SolverSpec(backend="naive", shuffle=True)),
        ("rgb", SolverSpec(backend="rgb", shuffle=True)),
        ("rgb-t8-c64", SolverSpec(backend="rgb", tile=8, chunk=64,
                                  shuffle=True)),
        ("kernel", SolverSpec(backend="kernel", shuffle=True)),
    ]
    if full:
        specs += [
            ("rgb-t128", SolverSpec(backend="rgb", tile=128,
                                    shuffle=True)),
            ("rgb-t32-c64", SolverSpec(backend="rgb", tile=32, chunk=64,
                                       shuffle=True)),
        ]
    return specs


def run(full: bool = False):
    B, m = (4096, 256) if full else (512, 64)
    lp = random_feasible_lp(jax.random.key(42), B, m)
    rows = []
    for label, spec in sweep_specs(full):
        solver = spec.build()
        dt = time_fn(solver.solve, lp)
        sol = solver.solve(lp)
        # Report the geometry the solve actually ran (unset tile/chunk
        # are pinned per shape by the table/heuristic), not the spec's
        # None sentinels — the trajectory row must name its launch.
        ran = spec.resolve_for_shape(m, B)
        row = {
            "bench": "solver_sweep",
            "label": label,
            "backend": ran.backend,
            "tile": ran.tile,
            "chunk": ran.chunk,
            "batch": B,
            "m": m,
            "seconds": dt,
            "us_per_lp": dt / B * 1e6,
            "n_feasible": int(sol.feasible.sum()),
        }
        print(json.dumps(row), flush=True)
        rows.append(emit(f"solver_sweep/b{B}/m{m}/{label}", dt,
                         f"per_lp_us={dt/B*1e6:.2f}"))
    return rows


if __name__ == "__main__":
    run(full=True)
