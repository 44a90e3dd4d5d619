"""The direct ``Solver`` path, closed loop.

A pool of distinct batches is made on the device from the seed in one
jitted call.  The caller solves one batch through ``Solver.solve``,
blocks on the result, then solves the next, cycling the pool, until the
window has run its length.  ``lps_per_s`` is the LPs answered over the
wall time of the whole window.  Afterwards a sample of the answers,
drawn from the seed, is judged against the reference.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import generators, roofline
from bench.device import describe
from bench.outcome import CompileCounter, Outcome, profiled


def run(cell, ctx) -> Outcome:
    import jax
    import jax.numpy as jnp
    from repro.core.lp import LPBatch
    from repro.solver import SolverSpec

    cfg, tr = cell.config, cell.traffic
    B, m, P = int(cfg["batch"]), int(tr["m"]), int(tr["pool"])
    dev = ctx.devices[0]

    def make_pool(key):
        out = []
        for k in jax.random.split(key, P):
            A, b, c = generators.random_feasible_lp(k, B, m)
            out.append((A, b, c, jnp.full((B,), m, jnp.int32)))
        return out

    with jax.default_device(dev):
        pool = jax.jit(make_pool)(generators.jax_key(ctx.seed))
    batches = [LPBatch(A=A, b=b, c=c, m_valid=mv) for A, b, c, mv in pool]
    solver = SolverSpec(backend=cfg["backend"], M=float(cfg["M"]),
                        dtype=cfg["dtype"]).build()
    for bt in batches:
        jax.block_until_ready(solver.solve(bt))
    ctx.log(f"direct B={B} m={m}: {solver.spec!r}")
    setup_s = time.perf_counter() - ctx.t_start

    outs = []
    # Set-up's objects are left out of the garbage collector's passes in
    # the window.
    gc.collect()
    gc.freeze()
    prof: dict = {}
    with CompileCounter() as compiles, profiled(ctx.trace, prof):
        t0 = time.perf_counter()
        end = t0 + ctx.seconds
        while True:
            sol = solver.solve(batches[len(outs) % P])
            jax.block_until_ready(sol)
            outs.append((sol.x, sol.feasible))
            if time.perf_counter() >= end:
                break
        t1 = time.perf_counter()
    gc.unfreeze()
    calls = len(outs)
    ctx.log(f"window: {calls} calls of {B} LPs in {t1 - t0:.6f} s, "
            f"{compiles.n} compiles inside the window {compiles.names}")
    device = describe(ctx.devices)

    # The sample: calls and rows drawn from the seed, pulled to the host
    # before the program's state is freed.
    rng = np.random.default_rng([ctx.seed, 0xD1])
    n = int(cell.limits["sample"])
    calls_s = rng.integers(0, calls, n)
    rows_s = rng.integers(0, B, n)
    A = np.empty((n, m, 2), np.float32)
    b = np.empty((n, m), np.float32)
    c = np.empty((n, 2), np.float32)
    x = np.empty((n, 2), np.float32)
    f = np.empty((n,), bool)
    for k in np.unique(calls_s):
        sel = np.nonzero(calls_s == k)[0]
        rows = rows_s[sel]
        bt = batches[k % P]
        A[sel] = np.asarray(bt.A[rows])
        b[sel] = np.asarray(bt.b[rows])
        c[sel] = np.asarray(bt.c[rows])
        x[sel] = np.asarray(outs[k][0][rows])
        f[sel] = np.asarray(outs[k][1][rows])
    del outs, batches, pool, solver, sol
    return Outcome(
        setup_s=setup_s,
        end_to_end={"lps_per_s": calls * B / (t1 - t0)},
        attempted=calls * B, failed=0, device=device,
        sample=[{"A": A, "b": b, "c": c, "m_valid": np.full(n, m),
                 "x": x, "feasible": f}],
        counters={"calls": calls, "lps": calls * B,
                  "kernel_bytes": calls * roofline.kernel_bytes(
                      B * m, B)},
        trace_path=prof.get("trace_path"),
        generator={"compiles_in_window": compiles.n})
