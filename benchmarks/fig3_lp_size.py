"""Paper Figure 3: solve time vs LP size at fixed batch counts.

Compares NaiveRGB (divergence-emulating vmap), RGB (cooperative tiles)
and the scipy/HiGHS per-problem CPU loop (the mGLPK/CLP stand-in
available in this container).  CPU wall-clock; the qualitative claim
reproduced is the *scaling* separation: RGB flattens with m thanks to
randomised order + tile early-exit while the CPU loop grows linearly in
batch and the naive version pays the full divergence cost.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import emit, time_fn
from repro.core import normalize_batch, random_feasible_lp, shuffle_batch
from repro.solver import SolverSpec

BATCHES = (128, 2048)
SIZES = (8, 32, 128, 512, 2048)
SCIPY_CAP = 256  # per-problem python loop gets slow; cap and extrapolate


def scipy_batch(lp) -> float:
    from scipy.optimize import linprog
    import time as _t
    A = np.asarray(lp.A, np.float64)
    b = np.asarray(lp.b, np.float64)
    c = np.asarray(lp.c, np.float64)
    n = min(lp.batch, SCIPY_CAP)
    t0 = _t.perf_counter()
    for i in range(n):
        linprog(-c[i], A_ub=A[i], b_ub=b[i],
                bounds=[(-1e4, 1e4)] * 2, method="highs")
    dt = _t.perf_counter() - t0
    return dt * (lp.batch / n)


def run(full: bool = False):
    rows = []
    batches = BATCHES if full else (128,)
    sizes = SIZES if full else (8, 64, 512)
    for B in batches:
        for m in sizes:
            lp = shuffle_batch(jax.random.key(1), normalize_batch(
                random_feasible_lp(jax.random.key(B + m), B, m)))
            for method in ("naive", "rgb", "kernel"):
                solver = SolverSpec(backend=method,
                                    normalize=False).build()
                dt = time_fn(solver.solve, lp)
                rows.append(emit(f"fig3/b{B}/m{m}/{method}", dt,
                                 f"per_lp_us={dt/B*1e6:.2f}"))
            dt = scipy_batch(lp)
            rows.append(emit(f"fig3/b{B}/m{m}/scipy-highs", dt,
                             f"per_lp_us={dt/B*1e6:.2f}"))
    return rows


if __name__ == "__main__":
    run(full=True)
