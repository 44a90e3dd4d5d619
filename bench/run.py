#!/usr/bin/env python3
"""The benchmark of the batch 2-D LP solver, one cell per run.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chips the cell asks for.  It makes its inputs from
the seed, warms every shape the cell uses (set-up), measures for
``--seconds``, judges a sample of the answers against the plain
reference, and prints one JSON line last on stdout:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``, each number
compared beside its limit.  With ``--trace 0`` the metrics are the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read from
the profiler's trace of the window and the program's counters.

With no TPU, or fewer chips than the cell asks for, it exits 2 and prints
no result.  The cell's files are found by name (``bench/cells.py``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import cells, device  # noqa: E402


def _num(v):
    """A JSON-safe number: non-finite values become strings."""
    v = float(v) if not isinstance(v, int) else v
    return v if not isinstance(v, float) or math.isfinite(v) else str(v)


def per_layer(cell, outcome, device_ids, peaks: dict) -> tuple:
    """The cell's per-layer metrics and the breakdown, from the trace of
    the window and the program's counters."""
    from bench import trace_reduce
    summary = trace_reduce.summarize(outcome.trace_path, device_ids)
    ctx = {"cell": cell, "outcome": outcome, "trace": summary,
           "peaks": peaks, "counters": outcome.counters,
           "n_devices": len(device_ids)}
    metrics = {}
    for m in cell.per_layer:
        value = cells.load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, summary


def run_cell(cell, *, seed: int, seconds: float, trace: bool, devices,
             t_start: float) -> dict:
    """Drive one run of ``cell`` on ``devices`` and return the result
    line as a dict.  The look for chips is the caller's."""
    import importlib
    from bench import check
    from bench.outcome import RunContext, remove_trace
    driver = importlib.import_module(f"bench.drivers.{cell.config['driver']}")
    ctx = RunContext(seed=seed, seconds=seconds, trace=trace,
                     devices=devices, t_start=t_start)
    outcome = driver.run(cell, ctx)
    peaks = device.peaks(outcome.device["kind"]) \
        if outcome.device["platform"] == "tpu" else {}
    correct, checks = check.verdict(check.judge(outcome.sample, cell.config),
                                    cell.limits, outcome.failed)
    dev = dict(outcome.device)
    line = {"correct": bool(correct), "attempted": int(outcome.attempted),
            "failed": int(outcome.failed)}
    if trace:
        try:
            metrics, summary = per_layer(cell, outcome,
                                         [d.id for d in devices], peaks)
        finally:
            remove_trace(outcome)
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        line["breakdown"] = summary.breakdown()
    else:
        metrics = {"setup_s": {"value": outcome.setup_s, "unit": "s"}}
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                continue
            if m["name"] not in outcome.end_to_end:
                raise KeyError(f"the driver did not measure {m['name']}")
            metrics[m["name"]] = {"value": _num(outcome.end_to_end[m["name"]]),
                                  "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = dev
    line["generator"] = {k: _num(v) for k, v in outcome.generator.items()}
    line["checks"] = {k: {"value": _num(v["value"]), "limit": v["limit"]}
                      for k, v in checks.items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    device.use_compile_cache(ROOT)
    try:
        devs = device.require_chips(cell.chips)
        device.peaks(devs[0].device_kind)
    except (device.NoChip, KeyError) as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 2
    line = run_cell(cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), devices=devs, t_start=T_START)
    for name, ch in line["checks"].items():
        print(f"[bench] check {name}: {ch['value']} (limit {ch['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
