"""Resolve a cell of ``BENCHMARK.json`` to its files.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by name:

- ``bench/configs/<config>.json``: the deployment (driver, sizes,
  scheduler settings, tolerance, source, ``reduced``, ``assumed``);
- ``bench/traffic/<traffic>.json``: the parameters the driver's
  generator reads;
- ``bench/limits/<workload>.json``: the correctness limits of the cell,
  with the readings they were set from;
- ``bench/metrics/<metric>.py``: the reader of one per-layer metric,
  ``read(ctx) -> float | None``;
- ``bench/drivers/<driver>.py``: the code that runs one kind of
  deployment, named by the configuration's ``driver``.

This module imports no JAX.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list      # the BENCHMARK.json entries this cell reports
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    return json.loads(path.read_text())


def resolve(workload: str, root: Path = ROOT) -> Cell:
    """The cell ``workload`` with its configuration, traffic, limits and
    the metrics it reports; raises if any of its files is missing."""
    spec = load_benchmark(root)
    entry = next((w for w in spec["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == entry["config"])
    config = _json(root / conf["file"])
    bench = root / "bench"
    traffic = _json(bench / "traffic" / f"{entry['traffic']}.json")
    limits = _json(bench / "limits" / f"{workload}.json")
    if not (bench / "drivers" / f"{config['driver']}.py").is_file():
        raise FileNotFoundError(f"no driver {config['driver']!r}")
    per_layer = [m for m in spec["per_layer"] if _applies(m, workload)]
    for m in per_layer:
        metric_path(m["name"], root)
    return Cell(name=workload, chips=int(entry["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, workload)],
                per_layer=per_layer)


def metric_path(name: str, root: Path = ROOT) -> Path:
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for per-layer metric {name!r}")
    return path


def load_reader(name: str, root: Path = ROOT):
    """The ``read`` function of a per-layer metric's file."""
    path = metric_path(name, root)
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
