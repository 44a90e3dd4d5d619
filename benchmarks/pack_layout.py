"""Packed-layout microbenchmark: what does pre-packing buy?

Three ways to solve the same batch stream through one Solver:

* ``aos``      — solve the AoS ``LPBatch`` (the solver packs inside the
  trace where the backend needs it);
* ``packed``   — pack once up front, solve the ``PackedLPBatch``
  repeatedly (the canonical serving shape: the layout prerequisite for
  double-buffered flushes);
* ``repack``   — re-pack the AoS batch *on every call* (the pre-refactor
  serving hot path, kept here as the regression baseline).

Emits one JSON row per (variant, backend) alongside the harness CSV
line, including the ``pack_calls`` each variant performed so the
no-repack claim is machine-checkable.  ``--smoke`` runs a CI-sized grid
and *asserts* that the pre-packed variant performs zero pack calls and
matches the AoS results bit-for-bit.

    python -m benchmarks.pack_layout          # quick grid
    python -m benchmarks.pack_layout --full   # paper-sized grid
    python -m benchmarks.pack_layout --smoke  # CI assertion mode
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from benchmarks.common import emit, time_fn
from repro.core import pack, pack_call_count, random_feasible_lp
from repro.solver import SolverSpec


def _specs(smoke: bool):
    specs = [("rgb", SolverSpec(backend="rgb"))]
    if smoke:
        specs.append(("kernel", SolverSpec(backend="kernel")))
    return specs


def run(full: bool = False, smoke: bool = False):
    if smoke:
        grid = [(64, 32)]
    elif full:
        grid = [(4096, 64), (4096, 512), (16384, 128)]
    else:
        grid = [(512, 64)]
    iters = 2 if smoke else 3
    rows = []
    for B, m in grid:
        lp = random_feasible_lp(jax.random.key(B + m), B, m)
        pb = pack(lp)
        for label, spec in _specs(smoke):
            solver = spec.build()
            variants = {
                "aos": lambda: solver.solve(lp),
                "packed": lambda: solver.solve(pb),
                "repack": lambda: solver.solve(pack(lp)),
            }
            results = {}
            for variant, fn in variants.items():
                n0 = pack_call_count()
                dt = time_fn(fn, warmup=1, iters=iters)
                n_calls = pack_call_count() - n0
                results[variant] = (dt, n_calls, fn())
                row = {
                    "bench": "pack_layout", "variant": variant,
                    "backend": label, "batch": B, "m": m,
                    "seconds": dt, "us_per_lp": dt / B * 1e6,
                    "pack_calls": n_calls,
                }
                print(json.dumps(row), flush=True)
                rows.append(emit(
                    f"pack_layout/b{B}/m{m}/{label}/{variant}", dt,
                    f"pack_calls={n_calls}"))
            if smoke:
                calls_packed = results["packed"][1]
                assert calls_packed == 0, (
                    f"pre-packed solve repacked {calls_packed}x on "
                    f"{label}")
                assert results["repack"][1] >= iters, (
                    "repack variant should pack per call")
                np.testing.assert_array_equal(
                    np.asarray(results["packed"][2].x),
                    np.asarray(results["aos"][2].x),
                    err_msg=f"packed != AoS on {label}")
    if smoke:
        print("pack_layout --smoke ok: pre-packed path does zero "
              "AoS->SoA repacks and matches AoS bit-for-bit")
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run asserting the no-repack claim")
    args = ap.parse_args(argv)
    run(full=args.full, smoke=args.smoke)


if __name__ == "__main__":
    main()
