"""CPU tests of the readers of the scheduler's per-flush host spans,
``assemble_ms.served_x4`` (``serve.assemble``) and
``settle_ms.served_x4`` (``serve.settle``), on synthetic traces laid
out as a v5e host's (``bench/host_spans.py``).

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_serve_spans.py -q
"""
from __future__ import annotations

import pytest

from bench import cells
from test_chip_trace import _Outcome, _xplane

READERS = {"assemble_ms.served_x4": "serve.assemble",
           "settle_ms.served_x4": "serve.settle"}
CELL = "served_ladder_x4.saturate"


def _read(name, path):
    ctx = {"outcome": _Outcome(path), "n_devices": 4}
    return cells.load_reader(name)(ctx)


@pytest.mark.parametrize("name", sorted(READERS))
def test_mean_of_the_spans_inside_the_window(tmp_path, name):
    span = READERS[name]
    other = ({*READERS.values(), "serve.dispatch"} - {span}).pop()
    host = [(span, 11, 0.25), (span, 15, 0.5), (span, 20, 0.75),
            (other, 12, 5.0)]                   # another stage's span
    path = _xplane(tmp_path, {0: [(12, 1)]}, host)
    assert _read(name, path) == pytest.approx(0.5)


@pytest.mark.parametrize("name", sorted(READERS))
def test_spans_are_clipped_to_the_window(tmp_path, name):
    span = READERS[name]
    # the window is 10..30 ms: 1 ms of the first span lies inside it,
    # 2 ms of the last, none of the one after it
    host = [(span, 9, 2.0), (span, 15, 3.0), (span, 28, 4.0),
            (span, 31, 9.0)]
    path = _xplane(tmp_path, {0: [(12, 1)]}, host)
    assert _read(name, path) == pytest.approx(2.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_without_the_span_reads_none(tmp_path, name):
    # the parent of these spans opens only serve.dispatch
    path = _xplane(tmp_path, {0: [(12, 1)]}, [("serve.dispatch", 11, 1)])
    assert _read(name, path) is None
    assert _read(name, None) is None


def test_the_readers_are_listed_for_the_four_chip_cell():
    per_layer = {m["name"]: m for m in cells.resolve(CELL).per_layer}
    for name in READERS:
        assert per_layer[name]["moves"] == "lps_per_s"
        assert per_layer[name]["layer"] == "scheduler (serve_lp/scheduler.py)"
