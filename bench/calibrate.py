#!/usr/bin/env python3
"""Measurements that fix a cell's parameters once, on the chip.

    python bench/calibrate.py knee --workload <served cell> --rates 2000,4000 --seconds 5
    python bench/calibrate.py limits --workload <cell> --seeds 12 --control 3 --seconds 3

``knee`` offers the cell's traffic at each rate in turn, from one
process that keeps its warmed scheduler, and prints what was answered,
the latency tails and the median latency of each quarter of the
requests (a backlog that grows shows as rising quarters).  The knee is
the highest rate at which it does not grow.

``limits`` gives the readings a cell's correctness limits are set from:
the program's ``flags_wrong`` and ``obj_gap`` on each seed (the lower
reading is their largest), and the control's on the first ``--control``
seeds: the reference in bfloat16 put in the program's place, judged on
the same sampled LPs (the upper reading is its smallest).
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import cells, check, device, reference  # noqa: E402
from bench.outcome import RunContext  # noqa: E402


def _driver(cell):
    import importlib
    return importlib.import_module(f"bench.drivers.{cell.config['driver']}")


def control_readings(sample, config) -> dict:
    """The control: the reference in bfloat16 answers the sample."""
    import ml_dtypes
    parts = []
    for g in sample:
        x, f = reference.solve(g["A"], g["b"], g["c"], g["m_valid"],
                               M=float(config["M"]),
                               eps=float(config["feasibility_tolerance"]),
                               dtype=ml_dtypes.bfloat16)
        parts.append(check.readings(
            g["A"], g["b"], g["c"], g["m_valid"], x, f,
            M=float(config["M"]), eps=float(config["feasibility_tolerance"])))
    return check.merge(parts)


def knee(cell, devs, rates, seconds, seed):
    keep: dict = {}
    for i, rate in enumerate(rates):
        cell.traffic["rate_per_s"] = float(rate)
        ctx = RunContext(seed=seed + i, seconds=seconds, trace=False,
                         devices=devs, t_start=time.perf_counter(),
                         keep=keep)
        out = _driver(cell).run(cell, ctx)
        row = {"rate_per_s": rate, **out.end_to_end, **out.generator,
               "p50_ms_by_quarter": out.counters["p50_ms_by_quarter"],
               "n_flushes": out.counters["n_flushes"]}
        print(json.dumps(row), flush=True)


def limits(cell, devs, seeds, n_control, seconds, first_seed):
    keep: dict = {}
    prog, ctrl = [], []
    for i in range(seeds):
        seed = first_seed + 7919 * i
        ctx = RunContext(seed=seed, seconds=seconds, trace=False,
                         devices=devs, t_start=time.perf_counter(),
                         keep=keep)
        out = _driver(cell).run(cell, ctx)
        t = time.perf_counter()
        r = check.judge(out.sample, cell.config)
        row = {"seed": seed, "program": r, "failed": out.failed,
               "reference_s": time.perf_counter() - t}
        prog.append(r)
        if i < n_control:
            row["control"] = control_readings(out.sample, cell.config)
            ctrl.append(row["control"])
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "lower": {"flags_wrong": max(r["flags_wrong"] for r in prog),
                  "obj_gap": max(r["obj_gap"] for r in prog)},
        "upper": {"flags_wrong": min(r["flags_wrong"] for r in ctrl),
                  "obj_gap": min(r["obj_gap"] for r in ctrl)}
        if ctrl else None}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("knee", "limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seed", type=int, default=1000)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    device.use_compile_cache(ROOT)
    try:
        devs = device.require_chips(cell.chips)
    except device.NoChip as e:
        print(f"[calibrate] {e}", file=sys.stderr)
        return 2
    if args.what == "knee":
        knee(cell, devs, [float(r) for r in args.rates.split(",")],
             args.seconds, args.seed)
    else:
        limits(cell, devs, args.seeds, args.control, args.seconds,
               args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
