"""CPU tests of the readers of the solver's stages and dispatch spans
(``bench/scopes.py``), on synthetic traces built as ``test_bench.py``
builds its own.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_scopes.py -q
"""
from __future__ import annotations

import dataclasses

import pytest

from bench import cells, scopes, trace_reduce

MS = 1_000_000                      # ns
WINDOW = (10 * MS, 30 * MS)
READERS = ("prepare_ms.direct", "pad_ms.direct", "finish_ms.direct",
           "dispatch_ms.direct")


def _stats(stats, ids):
    out = []
    for k, v in stats.items():
        esc = str(v).replace('"', '\\"')
        out.append(f'stats {{ metadata_id: {ids[k]} str_value: "{esc}" }}')
    return " ".join(out)


def _xplane(tmp_path, ops=(), host=(), window=WINDOW, name="t"):
    """A synthetic trace laid out as a v5e's: a TPU plane whose ``XLA
    Ops`` are ``(hlo, start_ns, dur_ns, tf_op)``, the scope path a stat
    of the event's metadata (``tf_op`` None: none), and a host plane
    with the bench.window annotation and ``(name, start_ns, dur_ns,
    stats)`` events."""
    ids = {"tf_op": 900, "batch": 901, "m": 902}
    smeta = " ".join(f'stat_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{k}" }} }}' for k, i in ids.items())
    meta, evs = [], []
    for i, (hlo, start, dur, tf_op) in enumerate(ops, 1):
        esc = hlo.replace('"', '\\"')
        st = _stats({"tf_op": tf_op}, ids) if tf_op is not None else ""
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{esc}" {st} }} }}')
        evs.append(f"events {{ metadata_id: {i} offset_ps: {start * 1000} "
                   f"duration_ps: {dur * 1000} }}")
    hmeta, hevs = [], []
    for i, (hname, start, dur, stats) in enumerate(host, 2):
        hmeta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{hname}" }} }}')
        hevs.append(f"events {{ metadata_id: {i} offset_ps: {start * 1000} "
                    f"duration_ps: {dur * 1000} {_stats(stats, ids)} }}")
    text = f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {" ".join(evs)} }}
  {" ".join(meta)} {smeta} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: {window[0] * 1000}
              duration_ps: {(window[1] - window[0]) * 1000} }}
    {" ".join(hevs)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  {" ".join(hmeta)} {smeta} }}
'''
    from jax.profiler import ProfileData
    path = tmp_path / f"{name}.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


@dataclasses.dataclass
class _Outcome:
    trace_path: str


def _ctx(path, calls=2):
    return {"outcome": _Outcome(path), "counters": {"calls": calls}}


def _read(path, calls=2):
    """Every reader on one run's context, as the harness calls them."""
    ctx = _ctx(path, calls)
    return {n: cells.load_reader(n)(ctx) for n in READERS}


KERNEL = ('%rgb_lp.1 = (f32[8,2]) custom-call(), '
          'custom_call_target="tpu_custom_call"')


def _op(hlo, start_ms, dur_ms, stage, leaf="op"):
    tf_op = None if stage is None else f"jit(solve)/{stage}/{leaf}:"
    return (hlo, int(start_ms * MS), int(dur_ms * MS), tf_op)


def _solve(t, pad=True):
    """One solve's ops from ``t`` ms: prepare 1, pad 0.5, kernel 4,
    finish 0.25 ms, and an XLA copy of an argument under no stage."""
    ops = [_op("%fusion.1 = f32[8] fusion()", t, 1, "solver.prepare",
               "mul"),
           _op("%copy.2 = f32[8] copy()", t + 1, 0.125, None)]
    if pad:
        ops.append(_op("%dynamic-update-slice.1 = f32[8] "
                       "dynamic-update-slice()", t + 1.125, 0.5,
                       "solver.pad", "scatter"))
    ops += [_op(KERNEL, t + 1.625, 4, "solver.kernel", "rgb_lp/pallas_call"),
            _op("%fusion.3 = f32[8] fusion()", t + 5.625, 0.25,
                "solver.finish", "bd,bd->b/dot_general")]
    return ops


def _span(t, dur_ms, m=64):
    return ("solver.solve", int(t * MS), int(dur_ms * MS),
            {"batch": 16384, "m": m})


def test_stage_times_per_call_inside_the_window(tmp_path):
    ops = _solve(4) + _solve(12) + _solve(20) + _solve(29.5)
    # the first solve lies before the window, the last is cut by its end:
    # of it, prepare's 0.5 ms counts
    path = _xplane(tmp_path, ops, [_span(3.9, 0.05), _span(11.9, 0.1),
                                   _span(19.9, 0.3)])
    got = _read(path, calls=2)
    assert got["prepare_ms.direct"] == pytest.approx((1 + 1 + 0.5) / 2)
    assert got["pad_ms.direct"] == pytest.approx(0.5)
    assert got["finish_ms.direct"] == pytest.approx(0.25)
    # spans in the window only: 0.1 and 0.3 ms
    assert got["dispatch_ms.direct"] == pytest.approx(0.2)


def test_stages_and_unscoped_ops_add_up_to_the_op_time(tmp_path):
    path = _xplane(tmp_path, _solve(12) + _solve(20) + _solve(29.5))
    st = scopes.read_trace(path)
    summary = trace_reduce.summarize(path, [0])
    ops_s = sum(st.stage_s.values()) + st.unscoped_s
    assert ops_s == pytest.approx(summary.ops_s)
    assert st.stage_s["solver.kernel"] == pytest.approx(summary.kernel_s)
    assert st.unscoped_s == pytest.approx(2 * 0.125e-3)
    frontend = summary.ops_s - summary.kernel_s
    assert ops_s - st.stage_s["solver.kernel"] == pytest.approx(frontend)


def test_a_stage_with_no_ops_reads_zero(tmp_path):
    # m a multiple of 128 and the batch of the tile: nothing to pad
    path = _xplane(tmp_path, _solve(12, pad=False) + _solve(20, pad=False),
                   [_span(11.9, 0.1, m=1024)])
    got = _read(path)
    assert got["pad_ms.direct"] == 0.0
    assert got["prepare_ms.direct"] == pytest.approx(1.0)


def test_a_program_without_stages_or_spans_reads_none(tmp_path):
    # as the program was before it named its stages: anonymous jit, ops
    # with scope paths of no solver stage, dispatch events of JAX's own
    ops = [("%fusion.1 = f32[8] fusion()", 12 * MS, MS, "jit(_lambda)/mul:"),
           (KERNEL, 13 * MS, 4 * MS, None)]
    path = _xplane(tmp_path, ops,
                   [("PjitFunction(_lambda)", 11 * MS, MS, {})])
    assert _read(path) == dict.fromkeys(READERS)
    # no trace at all
    assert cells.load_reader("pad_ms.direct")(_ctx(None)) is None


def test_ops_outside_the_window_are_not_solver_ops(tmp_path):
    path = _xplane(tmp_path, _solve(0), [_span(0, 0.1)])
    assert _read(path) == dict.fromkeys(READERS)


@pytest.mark.parametrize("tf_op,want", [
    ("jit(solve)/solver.pad/scatter:", "solver.pad"),
    # XLA merged two ops of one stage, then of two stages
    ("jit(solve)/solver.prepare/a;jit(solve)/solver.prepare/b:",
     "solver.prepare"),
    ("jit(solve)/solver.prepare/a;jit(solve)/solver.pad/b:", None),
    # a stage under an outer jit
    ("jit(step)/jit(solve)/solver.finish/slice:", "solver.finish"),
    # an XLA copy of an argument: its name alone
    ("batch.A:", None),
    ("", None),
    ("jit(solve)/solverXpad/x:", None),
])
def test_stage_of_an_op(tf_op, want):
    assert scopes.stage_of(tf_op) == want


def test_scope_paths_are_decoded_from_the_event_metadata(tmp_path):
    path = _xplane(tmp_path, _solve(12))
    with open(path, "rb") as f:
        table = scopes.op_scopes(f.read())
    assert table == {"/device:TPU:0": {
        hlo: tf_op for hlo, _, _, tf_op in _solve(12) if tf_op}}


def test_the_trace_is_read_once_per_run(tmp_path, monkeypatch):
    path = _xplane(tmp_path, _solve(12), [_span(11.9, 0.1)])
    calls = []
    real = scopes.read_trace

    def counting(p):
        calls.append(p)
        return real(p)
    monkeypatch.setattr(scopes, "read_trace", counting)
    _read(path)
    assert calls == [path]
    other = _xplane(tmp_path, _solve(20), name="u")
    _read(other)
    assert calls == [path, other]


def test_the_readers_are_listed_for_the_direct_cells():
    for name in READERS:
        m = next(m for m in cells.load_benchmark()["per_layer"]
                 if m["name"] == name)
        assert m["source"] == "device_trace" and m["unit"] == "ms"
        assert m["moves"] == "lps_per_s"
        assert m["workloads"] == ["paper_direct.m1024", "paper_direct.m64"]
        for w in m["workloads"]:
            assert name in {p["name"] for p in cells.resolve(w).per_layer}
