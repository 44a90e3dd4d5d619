"""CPU tests of the benchmark: trace reduction, metric arithmetic, the
generators' copies, cell resolution, the comparison that decides
``correct`` with its control, and faults planted under a run.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import json
import math
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench import (cells, check, generators, reference, roofline, stats,
                   trace_reduce)

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


# -- trace reduction --------------------------------------------------------

def _xplane(tmp_path, device_events, window=(0, 10_000_000),
            host_events=()):
    """A synthetic trace: one TPU plane with ``(hlo, start_ns, dur_ns)``
    ops and a host plane holding the bench.window annotation and
    ``(name, start_ns, dur_ns)`` host events."""
    hmeta, hevs = [], []
    for i, (name, start, dur) in enumerate(host_events, 2):
        hmeta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{name}" }} }}')
        hevs.append(f"events {{ metadata_id: {i} offset_ps: {start * 1000} "
                    f"duration_ps: {dur * 1000} }}")
    meta, evs = [], []
    for i, (name, start, dur) in enumerate(device_events, 1):
        esc = name.replace('"', '\\"')
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{esc}" }} }}')
        evs.append(f"events {{ metadata_id: {i} offset_ps: {start * 1000} "
                   f"duration_ps: {dur * 1000} }}")
    text = f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {" ".join(evs)} }}
  {" ".join(meta)} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: {window[0] * 1000}
              duration_ps: {(window[1] - window[0]) * 1000} }}
    {" ".join(hevs)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  {" ".join(hmeta)} }}
'''
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


KERNEL = '%k.1 = f32[8] custom-call(), custom_call_target="tpu_custom_call"'


def test_idle_is_one_minus_the_union_of_op_intervals(tmp_path):
    # ops [1, 3) and [2, 5) ms overlap: busy 4 ms of a 10 ms window
    path = _xplane(tmp_path, [(KERNEL, 1_000_000, 2_000_000),
                              ("%fusion.2 = f32[8] fusion()", 2_000_000,
                               3_000_000),
                              ("%copy.3 = f32[8] copy()", 9_500_000,
                               1_000_000)])
    s = trace_reduce.summarize(path, [0])
    assert s.window_s == pytest.approx(0.010)
    # the last op is clipped at the window's end: 0.5 ms counts
    assert s.busy_s == pytest.approx(0.0045)
    assert s.idle_pct() == pytest.approx(55.0)
    assert s.kernel_s == pytest.approx(0.002)
    assert s.kernel_n == 1
    assert s.ops_s == pytest.approx(0.0055)
    gaps = [g for _, g in s.gaps]
    assert gaps == pytest.approx([0.0045, 0.001])
    b = s.breakdown()
    assert b["device_ops"][0] == ["fusion.2", pytest.approx(0.003)]
    assert len(b["idle_gaps"]) == 2


def test_gaps_are_labelled_by_the_host_event_over_them(tmp_path):
    path = _xplane(tmp_path, [(KERNEL, 0, 1_000_000),
                              (KERNEL, 6_000_000, 4_000_000)],
                   host_events=[("PjitFunction(solve)", 500_000, 9_000_000),
                                ("ReadSyncFlag", 1_000_000, 5_000_000)])
    s = trace_reduce.summarize(path, [0])
    # of two events over the whole gap, the shorter says what ran
    assert s.gaps[0][0] == "ReadSyncFlag"
    assert s.gaps[0][1] == pytest.approx(0.005)


def test_union_and_gaps():
    assert stats.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert stats.gaps([(1, 2), (4, 5)], 0, 10) == [(5, 10), (2, 4), (0, 1)]


# -- metric arithmetic ------------------------------------------------------

def test_roofline_bytes_count_valid_constraints_only():
    # 3 words per valid constraint, 5 per problem (c, x, flag)
    assert roofline.kernel_bytes(1024 * 16384, 16384) == \
        4 * (3 * 1024 * 16384 + 5 * 16384)
    assert roofline.kernel_bytes(100, 2) == 4 * 310
    share = roofline.roofline_share(819e6, 0.1, 819e9)
    assert share == pytest.approx(1.0)
    assert roofline.roofline_share(1, 0.0, 819e9) is None


def test_shared_readers_give_idle_and_roofline_shares():
    trace = trace_reduce.Summary(
        window_s=2.0, busy_s=0.5, ops_s=0.5, kernel_s=0.25, kernel_n=1,
        n_devices=1, op_seconds={}, gaps=[])
    ctx = {"trace": trace, "counters": {"kernel_bytes": 819e6},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    for name in ("device_idle.direct", "device_idle.served",
                 "device_idle.served_x4"):
        assert cells.load_reader(name)(ctx) == pytest.approx(75.0)
    for name in ("kernel_roofline.direct", "kernel_roofline.served"):
        assert cells.load_reader(name)(ctx) == pytest.approx(0.4)
    ctx["trace"] = trace_reduce.Summary(
        window_s=2.0, busy_s=0.5, ops_s=0.5, kernel_s=0.0, kernel_n=0,
        n_devices=1, op_seconds={}, gaps=[])
    ctx["peaks"] = {}                   # no chip: no peaks either
    assert cells.load_reader("kernel_roofline.served")(ctx) is None


def test_served_readers_read_the_schedulers_counters():
    ctx = {"counters": {"queue_wait_s": 0.5, "queue_waits": 100,
                        "assemble_s": 0.2, "n_flushes": 50,
                        "padding_waste_cells": 0.25}}
    assert cells.load_reader("queue_wait_ms.served")(ctx) == \
        pytest.approx(5.0)
    assert cells.load_reader("host_ms_per_flush.served")(ctx) == \
        pytest.approx(4.0)
    assert cells.load_reader("cell_fill.served")(ctx) == pytest.approx(75.0)
    empty = {"counters": {"queue_wait_s": 0.0, "queue_waits": 0,
                          "assemble_s": 0.0, "n_flushes": 0}}
    for name in ("queue_wait_ms.served", "host_ms_per_flush.served",
                 "cell_fill.served"):
        assert cells.load_reader(name)(empty) is None


def test_percentiles_count_misses():
    lat = list(range(1, 101))              # 1..100 ms
    assert stats.percentile(lat, 50) == 50
    assert stats.percentile(lat, 99) == 99
    missed = lat[:98] + [math.inf, math.inf]
    assert stats.percentile(missed, 99) == math.inf
    assert stats.percentile(missed, 50) == 50


# -- the benchmark's files --------------------------------------------------

@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_to_its_files(workload):
    cell = cells.resolve(workload)
    assert cell.chips in (1, 4)
    assert (ROOT / "bench" / "drivers" /
            f"{cell.config['driver']}.py").is_file()
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(cells.load_reader(m["name"]))
        assert m["moves"] in names
    assert cell.limits["flags_wrong"] == 0
    assert 0 < cell.limits["obj_gap"] < 1


def test_benchmark_json_keeps_to_its_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    every = (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"]
             + SPEC["per_layer"])
    for e in every:
        assert NAME.match(e["name"]), e["name"]
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k], e
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["name"] == json.loads((ROOT / c["file"]).read_text())["name"]
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 2)
    for m in SPEC["per_layer"]:
        for w in m.get("workloads", []):
            assert m["moves"] in {e["name"] for e in
                                  cells.resolve(w).end_to_end}


def test_unknown_device_kind_is_an_error():
    from bench import device
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks("cpu")


def test_run_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper_direct.m64",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "{" not in r.stdout
    assert "not a TPU" in r.stderr


def test_importing_the_harness_loads_no_jax():
    code = ("import sys; sys.path[:0] = ['.', 'src']; import bench.run, "
            "bench.cells, bench.trace_reduce, bench.check; "
            "print('jax' in sys.modules)")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert r.stdout.strip() == "False", r.stderr


# -- the generators' copies -------------------------------------------------

def test_random_feasible_copy_reproduces_the_program():
    import jax
    from repro.core.lp import random_feasible_lp
    key = jax.random.key(7)
    A, b, c = generators.random_feasible_lp(key, 4, 16)
    want = random_feasible_lp(key, 4, 16)
    np.testing.assert_array_equal(np.asarray(A), np.asarray(want.A))
    np.testing.assert_array_equal(np.asarray(b), np.asarray(want.b))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(want.c))


def test_large_seeds_give_distinct_keys():
    import jax
    k1 = jax.random.key_data(generators.jax_key(2**31 + 5))
    k2 = jax.random.key_data(generators.jax_key(2**40 + 5))
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))


def test_ladder_keeps_the_served_bench_construction():
    kinds = np.asarray([0, 0, 1, 2, 2, 0])
    A, b, c, xstar = generators.ladder(generators.jax_key(3), kinds, 64)
    assert A.dtype == b.dtype == c.dtype == np.float32
    np.testing.assert_allclose(np.linalg.norm(A, axis=-1), 1, atol=1e-6)
    np.testing.assert_allclose(np.linalg.norm(c, axis=-1), 1, atol=1e-6)
    assert np.all((xstar >= -50) & (xstar < 50))
    for k in np.nonzero(kinds == 0)[0]:
        s = b[k] - A[k].astype(np.float64) @ xstar[k]
        assert np.all(s > 0.1 - 1e-4) and np.all(s < 5 + 1e-4)
    for k in np.nonzero(kinds == 2)[0]:
        # every constraint passes through one point
        p = np.linalg.solve(A[k, :2].astype(np.float64), b[k, :2])
        assert np.max(np.abs(A[k] @ p - b[k])) < 1e-3
    for k in np.nonzero(kinds == 1)[0]:
        np.testing.assert_array_equal(A[k, :2], [[1, 0], [-1, 0]])
        np.testing.assert_array_equal(b[k, :2], [-1, -1])


def test_traffic_draws_the_ladder_and_the_mix_in_whole_shares():
    from bench.drivers import served
    tr = json.loads((ROOT / "bench/traffic/poisson.json").read_text())
    reqs, ms, kinds = served._requests(tr, 1000, np.random.default_rng(5),
                                       generators.jax_key(5))
    assert sorted(np.unique(ms)) == tr["m_ladder"]
    assert all(np.sum(ms == m) == 100 for m in tr["m_ladder"])
    assert np.bincount(kinds).tolist() == [800, 100, 100]
    assert all(r[0].shape == (m, 2) for r, m in zip(reqs, ms))
    # another seed: the same multiset in another order
    _, ms2, kinds2 = served._requests(tr, 1000, np.random.default_rng(6),
                                      generators.jax_key(6))
    assert sorted(ms2) == sorted(ms) and not np.array_equal(ms2, ms)
    due = served._due_times(1000.0, 1000, 1.0, np.random.default_rng(1))
    assert due[-1] == pytest.approx(1.0) and np.all(np.diff(due) > 0)


# -- the comparison and its control -----------------------------------------

def _direct_sample(m, n, seed):
    import jax
    A, b, c = jax.jit(generators.random_feasible_lp,
                      static_argnums=(1, 2))(generators.jax_key(seed), n, m)
    return np.asarray(A), np.asarray(b), np.asarray(c)


def _program(A, b, c):
    from repro.core.lp import make_batch
    from repro.solver import SolverSpec
    sol = SolverSpec(backend="rgb").build().solve(make_batch(A, b, c))
    return np.asarray(sol.x), np.asarray(sol.feasible)


CFG = {"M": 1e4, "feasibility_tolerance": 1e-5}


def _limits(workload):
    """A cell's limits, read from its file: a cell waiting under Open
    questions has them too."""
    return json.loads((ROOT / "bench" / "limits" /
                       f"{workload}.json").read_text())


@pytest.mark.parametrize("workload,m", [("paper_direct.m1024", 256),
                                        ("paper_direct.m64", 64)])
def test_direct_program_passes_and_control_fails(workload, m):
    lim = _limits(workload)
    A, b, c = _direct_sample(m, 64, 11)
    x, f = _program(A, b, c)
    g = {"A": A, "b": b, "c": c, "m_valid": np.full(64, m)}
    ok, _ = check.verdict(check.judge([{**g, "x": x, "feasible": f}], CFG),
                          lim, 0)
    assert ok
    import ml_dtypes
    xc, fc = reference.solve(A, b, c, g["m_valid"], M=1e4, eps=1e-5,
                             dtype=ml_dtypes.bfloat16)
    ok, checks = check.verdict(
        check.judge([{**g, "x": xc, "feasible": fc}], CFG), lim, 0)
    assert not ok, checks


@pytest.mark.parametrize("workload", ["served_ladder.poisson"])
def test_served_program_passes_and_control_fails(workload):
    import ml_dtypes
    lim = _limits(workload)
    rng = np.random.default_rng(2)
    kinds = generators.stratified(rng, 40, [0.8, 0.1, 0.1])
    sample, control = [], []
    for m in (8, 64, 512):
        A, b, c, _ = generators.ladder(generators.jax_key(m), kinds, m)
        x, f = _program(A, b, c)
        g = {"A": A, "b": b, "c": c, "m_valid": np.full(len(kinds), m)}
        sample.append({**g, "x": x, "feasible": f})
        xc, fc = reference.solve(A, b, c, g["m_valid"], M=1e4, eps=1e-5,
                                 dtype=ml_dtypes.bfloat16)
        control.append({**g, "x": xc, "feasible": fc})
    assert check.verdict(check.judge(sample, CFG), lim, 0)[0]
    assert not check.verdict(check.judge(control, CFG), lim, 0)[0]


def test_degenerate_lps_may_be_called_either_way():
    A, b, c, _ = generators.ladder(generators.jax_key(9), np.full(16, 2),
                                   128)
    mv = np.full(16, 128)
    x, f = reference.solve(A, b, c, mv, M=1e4, eps=1e-5)
    assert f.all()                          # feasible within the tolerance
    _, tight = reference.solve(A, b, c, mv, M=1e4, eps=-1e-5)
    assert not tight.any()                  # but not with room to spare
    r = check.readings(A, b, c, mv, x, np.zeros(16, bool), M=1e4, eps=1e-5)
    assert r["flags_wrong"] == 0


# -- faults planted under a run ---------------------------------------------

def _cell_files(config, traffic, chips, end_to_end=("lps_per_s",)):
    """A cell built from its files without an entry in BENCHMARK.json:
    the served configurations and their mixes wait there for their
    measurement (PERF.md, Open questions)."""
    bench = ROOT / "bench"
    return cells.Cell(
        name=f"{config}.{traffic}", chips=chips,
        config=json.loads((bench / "configs" / f"{config}.json").read_text()),
        traffic=json.loads((bench / "traffic" / f"{traffic}.json").read_text()),
        limits={"flags_wrong": 0, "obj_gap": 2e-3, "sample_per_m": 100},
        end_to_end=[{"name": "setup_s", "unit": "s"}] +
                   [{"name": n, "unit": "ms" if n.endswith("_ms") else "LP/s"}
                    for n in end_to_end],
        per_layer=[])


def _tiny(workload):
    if workload == "served_ladder_x4.saturate":
        cell = _cell_files("served_ladder_x4", "saturate", 4)
    elif workload == "served_ladder.poisson":
        cell = _cell_files("served_ladder", "poisson", 1,
                           ("p50_ms", "p99_ms"))
    else:
        cell = cells.resolve(workload)
    if cell.config["driver"] == "direct":
        cell.config["batch"] = 64
        cell.traffic["m"] = 32
        cell.traffic["pool"] = 2
        cell.limits["sample"] = 64
        cell.config["backend"] = "rgb"
    else:
        cell.config["scheduler"]["max_batch"] = 8
        cell.config["backend"] = "rgb"
        cell.traffic["m_ladder"] = [8, 16, 40]
        if "rate_per_s" in cell.traffic:
            cell.traffic["rate_per_s"] = 200.0
        else:
            # flushes of several tiles, so that rows reach every chip
            cell.config["scheduler"]["max_batch"] = 128
            cell.traffic["requests_per_s_cap"] = 1000.0
        cell.limits["sample_per_m"] = 30
    return cell


def _run(cell, devices=None):
    import jax
    from bench import run
    return run.run_cell(cell, seed=2**31 + 11, seconds=0.5, trace=False,
                        devices=devices or jax.devices()[:1],
                        t_start=time.perf_counter())


def test_direct_run_is_correct_unbroken():
    line = _run(_tiny("paper_direct.m64"))
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["metrics"]["lps_per_s"]["value"] > 0


def _patch_solve(monkeypatch, alter):
    from repro.core.lp import LPSolution
    from repro.solver import solver as solver_mod
    orig = solver_mod.Solver.solve

    def broken(self, batch, key=None):
        sol = orig(self, batch, key)
        x, f = alter(sol.x, sol.feasible)
        return LPSolution(x=x, feasible=f, objective=sol.objective)
    monkeypatch.setattr(solver_mod.Solver, "solve", broken)


def test_direct_answer_altered_is_not_correct(monkeypatch):
    _patch_solve(monkeypatch, lambda x, f: (x.at[3, 0].add(1.0), f))
    cell = _tiny("paper_direct.m64")
    cell.limits["sample"] = 512        # every row of every call, likely
    assert not _run(cell)["correct"]


def test_direct_half_batch_left_out_is_not_correct(monkeypatch):
    def half(x, f):
        n = x.shape[0] // 2
        return x.at[n:].set(0.0), f
    _patch_solve(monkeypatch, half)
    assert not _run(_tiny("paper_direct.m64"))["correct"]


def _patch_complete(monkeypatch, alter):
    from repro.serve_lp import sharding
    orig = sharding.Executable.complete

    def broken(self, handle):
        x, f = orig(self, handle)
        return alter(np.array(x), np.array(f), self)
    monkeypatch.setattr(sharding.Executable, "complete", broken)


def test_served_run_is_correct_unbroken():
    line = _run(_tiny("served_ladder.poisson"))
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"p50_ms", "p99_ms", "setup_s"}
    # the warm-up reached every executable the window used
    assert line["generator"]["compiles_in_window"] == 0


def test_served_answer_altered_is_not_correct(monkeypatch):
    def alter(x, f, exe):
        x[0, 1] += 1.0
        f[:] = f
        return x, f
    _patch_complete(monkeypatch, alter)
    assert not _run(_tiny("served_ladder.poisson"))["correct"]


def test_served_half_batch_left_out_is_not_correct(monkeypatch):
    def alter(x, f, exe):
        x[::2] = 0.0                   # every other row never solved
        return x, f
    _patch_complete(monkeypatch, alter)
    assert not _run(_tiny("served_ladder.poisson"))["correct"]


FOUR = """
import sys, json
sys.path[:0] = ['.', 'src', 'bench/tests']
import numpy as np, jax
import test_bench as T
from repro.serve_lp import sharding
fault = sys.argv[1]
if fault == "exchange":
    orig = sharding.Executable.complete
    def broken(self, handle):
        x, f = orig(self, handle)
        x, f = np.array(x), np.array(f)
        keep = self.layout.shards[0] if self.layout is not None else len(x)
        x[keep:] = 0.0               # only the first chip's rows came back
        return x, f
    sharding.Executable.complete = broken
line = T._run(T._tiny("served_ladder_x4.saturate"), jax.devices()[:4])
print(json.dumps({"correct": line["correct"],
                  "lps": line["metrics"]["lps_per_s"]["value"]}))
"""


@pytest.mark.parametrize("fault,correct", [("none", True),
                                           ("exchange", False)])
def test_four_chip_exchange_left_out_is_not_correct(fault, correct):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", FOUR, fault], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"] is correct
