#!/usr/bin/env python3
"""Smoke run of the batch LP solver's main paths on a TPU.

    python chip_smoke.py                # one chip: direct, served, RPC
    python chip_smoke.py --four-chips   # 4-device vs 1-device scheduler

One process does everything: it holds the chip, and the RPC server runs
in a thread of it.  With no TPU it fails before any work.  Each phase
checks its answers: the direct path against ``backend="naive"`` on the
chip and scipy ``linprog`` on the host, the served and RPC paths
against direct solves with the same spec.  The timings printed are
smoke timings (compile included, one run each), not benchmark numbers.

The last line of stdout is the result, printed only when every phase
passed: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import http.client
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

BATCH = 16384                 # paper Fig. 3c batch
DIRECT_M = (64, 1024, 8192)
NAIVE_ROWS_AT_8192 = 1024
SCIPY_ROWS = 32
SERVED_REQUESTS = 3000
SERVED_M_MAX = 4096           # bench traffic ladder, m from 8 to 4096
FOUR_CHIP_REQUESTS = 2000
# Tolerances of the repo's tests: cross-backend objectives
# (tests/test_solver.py TOL_5SIG), scipy objectives (tests/test_lp_core.py
# RTOL), scheduler-vs-direct x (serve_lp.bench's check).
TOL_BACKEND = 5e-4
TOL_SCIPY = 3e-4
TOL_SERVED = 1e-5


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def timing(phase: str, seconds: float) -> None:
    log(f"smoke timing (not a benchmark): {phase} {seconds:.3f}s")


def require_tpu(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found platform {devs[0].platform!r} "
             f"({devs[0].device_kind}); this smoke runs only on a chip")
    if len(devs) < n:
        fail(f"needs {n} TPU devices, JAX found {len(devs)}")
    return devs


def _assert_close(name, got, want, tol):
    err = np.max(np.abs(got - want) / (tol + tol * np.abs(want)),
                 initial=0.0)
    check(err <= 1.0, f"{name}: max error {err:.3g}x the tolerance {tol}")


def scipy_objectives(lp, rows, M):
    from scipy.optimize import linprog
    A, b, c = (np.asarray(a, np.float64) for a in (lp.A, lp.b, lp.c))
    out = []
    for i in rows:
        r = linprog(-c[i], A_ub=A[i], b_ub=b[i], bounds=[(-M, M)] * 2,
                    method="highs")
        check(r.status == 0, f"scipy status {r.status} on row {i}")
        out.append(-r.fun)
    return np.asarray(out)


def direct_phase(seed: int) -> None:
    import jax
    from repro.core.lp import LPBatch, random_feasible_lp
    from repro.solver import SolverSpec

    gen = jax.jit(random_feasible_lp, static_argnums=(1, 2))
    naive = SolverSpec(backend="naive").build()
    for m in DIRECT_M:
        lp = gen(jax.random.key(seed + m), BATCH, m)
        spec = SolverSpec(backend="auto")
        r = spec.resolve_for_shape(m, BATCH)
        log(f"direct B={BATCH} m={m}: backend={r.backend} "
            f"interpret={r.interpret} tile={r.tile} chunk={r.chunk}")
        check(r.backend == "kernel", f"auto resolved to {r.backend}")
        check(r.interpret is False, "kernel resolved to interpret mode")
        solver = spec.build()
        t0 = time.perf_counter()
        sol = jax.block_until_ready(solver.solve(lp))
        timing(f"direct m={m} first call (compile included)",
               time.perf_counter() - t0)
        t0 = time.perf_counter()
        sol = jax.block_until_ready(solver.solve(lp))
        timing(f"direct m={m} second call", time.perf_counter() - t0)
        x = np.asarray(sol.x)
        feas = np.asarray(sol.feasible)
        obj = np.asarray(sol.objective)
        check(x.shape == (BATCH, 2), f"x shape {x.shape}")
        check(bool(feas.all()), f"m={m}: {int((~feas).sum())} feasible "
              "LPs reported infeasible")
        check(bool(np.isfinite(x).all()), f"m={m}: non-finite x")

        n = BATCH if m <= 1024 else NAIVE_ROWS_AT_8192
        ref_lp = lp if n == BATCH else LPBatch(
            A=lp.A[:n], b=lp.b[:n], c=lp.c[:n], m_valid=lp.m_valid[:n])
        ref = naive.solve(ref_lp)
        check(np.array_equal(feas[:n], np.asarray(ref.feasible)),
              f"m={m}: feasibility differs from naive")
        _assert_close(f"m={m} objective vs naive", obj[:n],
                      np.asarray(ref.objective), TOL_BACKEND)
        log(f"direct m={m}: {n} rows agree with backend=naive")

        rows = np.linspace(0, BATCH - 1, SCIPY_ROWS).astype(int)
        _assert_close(f"m={m} objective vs scipy", obj[rows],
                      scipy_objectives(lp, rows, spec.M), TOL_SCIPY)
        log(f"direct m={m}: {SCIPY_ROWS} rows agree with scipy linprog")

        if m == DIRECT_M[-1]:
            chunked = SolverSpec(backend="kernel", chunk=128)
            log(f"chunked B={BATCH} m={m}: "
                f"{chunked.resolve_for_shape(m, BATCH)}")
            t0 = time.perf_counter()
            cs = jax.block_until_ready(chunked.build().solve(lp))
            timing(f"chunked m={m} first call (compile included)",
                   time.perf_counter() - t0)
            check(np.array_equal(np.asarray(cs.feasible), feas),
                  "chunked feasibility differs from dense")
            _assert_close("chunked objective vs dense",
                          np.asarray(cs.objective), obj, TOL_BACKEND)
            log(f"chunked m={m}: agrees with the dense kernel "
                f"(bit-identical x: {np.array_equal(np.asarray(cs.x), x)})")
        del lp, sol, ref


def _direct_solutions(spec, reqs):
    from repro.core import make_batch
    from repro.solver import get_solver
    solver = get_solver(spec)
    return [solver.solve(make_batch(A, b, c)) for A, b, c, _ in reqs]


def _served(devices, reqs):
    from repro.serve_lp.scheduler import BatchScheduler
    from repro.solver import SolverSpec
    sched = BatchScheduler(SolverSpec(backend="kernel"), devices=devices,
                           max_batch=256)
    t0 = time.perf_counter()
    with sched:
        futs = [sched.submit(A, b, c) for A, b, c, _ in reqs]
    results = [f.result(timeout=900) for f in futs]
    timing(f"served {len(reqs)} requests on {len(devices)} device(s) "
           "(compile included)", time.perf_counter() - t0)
    return results, sched.metrics.snapshot()


def _traffic(seed: int, n: int):
    from repro.serve_lp.bench import BenchConfig, make_request
    cfg = BenchConfig(m_min=8, m_max=SERVED_M_MAX, seed=seed)
    return [make_request(cfg, i) for i in range(n)]


def served_phase(seed: int, devs) -> None:
    from repro.solver import SolverSpec
    reqs = _traffic(seed, SERVED_REQUESTS)
    kinds = {k: sum(r[3] == k for r in reqs) for k in
             ("feasible", "infeasible", "degenerate")}
    ms = sorted({r[0].shape[0] for r in reqs})
    log(f"served traffic: {len(reqs)} requests, kinds {kinds}, m {ms}")
    results, snap = _served(devs[:1], reqs)
    log(f"served: {snap['n_flushes']} flushes, rows per device "
        f"{snap['rows_per_device']}")
    check(sum(r.feasible for r in results) > 0, "no feasible results")
    idx = np.linspace(0, len(reqs) - 1, 64).astype(int)
    direct = _direct_solutions(SolverSpec(backend="kernel"),
                               [reqs[i] for i in idx])
    for i, sol in zip(idx, direct):
        r = results[i]
        check(bool(sol.feasible[0]) == r.feasible,
              f"served request {i}: feasibility differs from direct")
        if r.feasible:
            _assert_close(f"served request {i} x", r.x,
                          np.asarray(sol.x[0]), TOL_SERVED)
    log(f"served: {len(idx)} sampled requests match direct solves")


def rpc_phase(seed: int, devs) -> None:
    from repro.serve_lp.rpc import make_frontend
    from repro.serve_lp.rpc.server import run_in_thread
    from repro.solver import SolverSpec
    spec = SolverSpec(backend="kernel")
    reqs = [r for r in _traffic(seed + 1, 64)
            if r[0].shape[0] <= 1024][:20]
    frontend = make_frontend(spec, devices=devs[:1], max_batch=256)
    port, stop = run_in_thread(frontend)
    t0 = time.perf_counter()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)

        def post(obj):
            conn.request("POST", "/v1/solve", json.dumps(obj),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = json.loads(resp.read() or b"{}")
            check(resp.status == 200, f"POST /v1/solve: {resp.status} "
                  f"{body}")
            return body

        def as_json(r):
            return {"A": r[0].tolist(), "b": r[1].tolist(),
                    "c": r[2].tolist()}

        answers = [post(as_json(r))["result"] for r in reqs[:4]]
        for lo in (4, 12):
            body = post({"problems": [as_json(r) for r in
                                      reqs[lo:lo + 8]]})
            check(body["n"] == 8, f"batch answered {body['n']} of 8")
            answers += body["results"]
        conn.close()
    finally:
        stop()
    timing(f"rpc {len(answers)} problems (compile included)",
           time.perf_counter() - t0)
    direct = _direct_solutions(spec, reqs[:len(answers)])
    for i, (a, sol) in enumerate(zip(answers, direct)):
        check(a["feasible"] == bool(sol.feasible[0]),
              f"rpc answer {i}: feasibility differs from direct")
        if a["feasible"]:
            _assert_close(f"rpc answer {i} x", np.asarray(a["x"]),
                          np.asarray(sol.x[0]), TOL_SERVED)
    log(f"rpc: {len(answers)} answers (4 single, 2 batches of 8) match "
        "direct solves")


def four_chip_phase(seed: int, devs) -> None:
    reqs = _traffic(seed, FOUR_CHIP_REQUESTS)
    r4, snap4 = _served(devs[:4], reqs)
    r1, _ = _served(devs[:1], reqs)
    rows = snap4["rows_per_device"]
    log(f"4-device scheduler: {snap4['n_flushes']} flushes, "
        f"{snap4['launches_total']} launches, rows per device {rows}")
    check(len(rows) == 4 and all(n > 0 for n in rows),
          f"not every device received rows: {rows}")
    x4 = np.stack([r.x for r in r4])
    x1 = np.stack([r.x for r in r1])
    f4 = np.asarray([r.feasible for r in r4])
    f1 = np.asarray([r.feasible for r in r1])
    check(np.array_equal(f4, f1), "feasible differs, 4 vs 1 device")
    check(np.array_equal(x4, x1, equal_nan=True),
          "x differs, 4 vs 1 device")
    log(f"4 vs 1 device: {len(reqs)} results bit-identical "
        f"({int(f4.sum())} feasible)")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device vs 1-device served phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated LP")
    args = ap.parse_args(argv)

    devs = require_tpu(4 if args.four_chips else 1)
    try:
        from repro.compile_cache import use_compile_cache
    except ImportError as e:
        fail(f"the repro package is not next to this script: {e}")
    log(f"compile cache: {use_compile_cache()}")
    log(f"devices: {len(devs)} x {devs[0].device_kind}")

    if args.four_chips:
        four_chip_phase(args.seed, devs)
    else:
        direct_phase(args.seed)
        served_phase(args.seed, devs)
        rpc_phase(args.seed, devs)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
