"""CPU tests of the four-chip cell's trace readers (``bench/chip_trace.py``:
per-chip busy time and the scheduler's ``serve.dispatch`` spans), on
synthetic traces laid out as a v5e host's: one plane per TPU and a host
plane holding the bench.window annotation.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_chip_trace.py -q
"""
from __future__ import annotations

import dataclasses

import pytest

from bench import cells, chip_trace, trace_reduce

MS = 1_000_000                      # ns
WINDOW = (10 * MS, 30 * MS)
CELL = "served_ladder_x4.saturate"
READERS = ("busy_imbalance.served_x4", "dispatch_ms.served_x4")


def _xplane(tmp_path, chips, host=(), window=WINDOW):
    """``chips`` maps a TPU id to its ``XLA Ops`` as ``(start_ms,
    dur_ms)``; ``host`` holds ``(name, start_ms, dur_ms)`` events beside
    the window annotation."""
    planes = []
    for pid, (dev, ops) in enumerate(sorted(chips.items()), 1):
        evs = " ".join(
            f"events {{ metadata_id: 1 offset_ps: {int(s * MS) * 1000} "
            f"duration_ps: {int(d * MS) * 1000} }}" for s, d in ops)
        planes.append(
            f'planes {{ id: {pid} name: "/device:TPU:{dev}" '
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {evs} }} '
            f'event_metadata {{ key: 1 value {{ id: 1 '
            f'name: "%fusion.1 = f32[8] fusion()" }} }} }}')
    names = sorted({h[0] for h in host})
    ids = {n: i for i, n in enumerate(names, 2)}
    hmeta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for n, i in ids.items())
    hevs = " ".join(f"events {{ metadata_id: {ids[n]} "
                    f"offset_ps: {int(s * MS) * 1000} "
                    f"duration_ps: {int(d * MS) * 1000} }}"
                    for n, s, d in host)
    planes.append(f'''planes {{ id: 99 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: {window[0] * 1000}
              duration_ps: {(window[1] - window[0]) * 1000} }}
    {hevs} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  {hmeta} }}''')
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        "\n".join(planes)))
    return str(path)


@dataclasses.dataclass
class _Outcome:
    trace_path: str


def _read(path, n_devices=4):
    """Every reader on one run's context, as the harness calls them."""
    ctx = {"outcome": _Outcome(path), "n_devices": n_devices}
    return {n: cells.load_reader(n)(ctx) for n in READERS}


def test_busy_imbalance_is_the_busiest_chip_over_the_mean(tmp_path):
    # chip 0: 4 ms busy (two overlapping ops count once); chips 1 and
    # 2: 2 ms; chip 3 has no op at all.  Mean 2 ms, busiest 4 ms.
    path = _xplane(tmp_path, {0: [(12, 3), (14, 2)], 1: [(12, 2)],
                              2: [(20, 2)]})
    got = _read(path)
    assert got["busy_imbalance.served_x4"] == pytest.approx(2.0)


def test_busy_time_counts_only_the_window(tmp_path):
    # chip 0's ops start 1 ms before the window and run 2 ms past its
    # end: 1 ms and 1 ms of them are inside
    path = _xplane(tmp_path, {0: [(9, 2), (29, 3)], 1: [(15, 2)]})
    devices, host = trace_reduce.read_events(path)
    busy = chip_trace.busy_s(devices, host)
    assert busy[0] == pytest.approx(2e-3)
    assert busy[1] == pytest.approx(2e-3)
    assert _read(path, n_devices=2)["busy_imbalance.served_x4"] == \
        pytest.approx(1.0)


def test_dispatch_ms_is_the_mean_serve_dispatch_span(tmp_path):
    host = [("serve.dispatch", 11, 0.25), ("serve.dispatch", 15, 0.5),
            ("serve.dispatch", 20, 0.75),
            ("serve.dispatch", 40, 9.0),        # after the window
            ("solver.solve", 12, 5.0)]          # another span
    path = _xplane(tmp_path, {0: [(12, 1)]}, host)
    assert _read(path)["dispatch_ms.served_x4"] == pytest.approx(0.5)


def test_a_program_without_dispatch_spans_reads_none(tmp_path):
    path = _xplane(tmp_path, {0: [(12, 1)], 1: [(12, 1)], 2: [(12, 1)],
                              3: [(12, 1)]})
    got = _read(path)
    assert got["dispatch_ms.served_x4"] is None
    assert got["busy_imbalance.served_x4"] == pytest.approx(1.0)


def test_no_trace_or_no_busy_chip_reads_none(tmp_path):
    assert _read(None) == dict.fromkeys(READERS)
    path = _xplane(tmp_path, {0: [(40, 1)]})          # after the window
    assert _read(path)["busy_imbalance.served_x4"] is None


def test_the_trace_is_read_once_per_run(tmp_path, monkeypatch):
    path = _xplane(tmp_path, {0: [(12, 1)]}, [("serve.dispatch", 11, 1)])
    calls = []
    orig = trace_reduce.read_events

    def counting(p):
        calls.append(p)
        return orig(p)
    monkeypatch.setattr(trace_reduce, "read_events", counting)
    _read(path)
    assert calls == [path]


def test_the_readers_are_listed_for_the_four_chip_cell():
    names = {m["name"] for m in cells.resolve(CELL).per_layer}
    assert set(READERS) <= names
