"""The flush pin: ``BatchScheduler.flush_shape`` resolves a flush's
geometry on the whole flush (explicit > tuning table > heuristic) and
pads it up the batch ladder; the mesh planner deals its whole tiles;
the SLO controller predicts flush cost from the same method; each
flush's dispatch is one ``serve.dispatch`` profiler span.

The pin itself needs only the device count, so its sweep runs in this
process; everything that builds or runs executables on a mesh runs out
of process on four forced host devices (``conftest.run_multidevice``).
"""
import dataclasses
import json
from pathlib import Path

import jax
import pytest

from repro.kernels.batch_lp import _pick_tile
from repro.serve_lp import BatchScheduler, SolverSpec, plan_layout
from repro.serve_lp.rpc.slo import flush_layout
from repro.tune import (TuningTable, current_device_kind, default_table,
                        use_table)

REPO = Path(__file__).resolve().parents[1]


def _v5e_table():
    """The bundled table's TPU kernel rows (tile 128 for every m bucket
    up to 1024, batch buckets from 256), keyed to this process's device
    kind so resolution here sees what a v5e sees."""
    here = current_device_kind()
    return TuningTable(
        dataclasses.replace(e, key=dataclasses.replace(e.key,
                                                       device_kind=here))
        for e in default_table().entries()
        if e.key.device_kind == "tpu" and e.key.backend == "kernel")


@pytest.mark.parametrize("bm", [128, 1024, 4096])
@pytest.mark.parametrize("n", [1, 2, 4])
def test_flush_shape_pins_the_whole_flush(n, bm):
    """Every flush size on n devices: the tile is the shape-resolved
    tile of the whole flush, whatever n; ``b_pad`` is the flush rounded
    up the tile ladder; the planner deals whole tiles, never to more
    devices than there are tiles; padding stays under one tile unless
    VMEM holds the tile below the flush, and then under half the padded
    flush; the SLO controller's layout is the scheduler's."""
    spec = SolverSpec(backend="kernel")
    vmem_tile = _pick_tile(bm)              # 128 up to m 1024, 48 at 4096
    with use_table(_v5e_table()):
        sched = BatchScheduler(spec, devices=jax.devices()[:1] * n)
        for B in range(1, 257):
            pinned, b_pad = sched.flush_shape(bm, B)
            tile = pinned.tile
            assert tile == spec.resolve_for_shape(bm, B).tile, B
            lay = plan_layout(b_pad, tile, n)
            assert lay.b_pad == b_pad >= B
            assert all(s % tile == 0 for s in lay.shards), (B, lay)
            assert lay.used_devices == min(n, b_pad // tile), (B, lay)
            if tile >= B:
                assert b_pad == tile and b_pad - B < 8, (B, tile)
            else:       # VMEM (or the table's equal row) holds the tile
                assert tile == vmem_tile, (B, tile)
                assert b_pad - B < b_pad / 2, (B, lay)
            assert flush_layout(sched, bm, B) == lay


def test_v5e_table_places_a_full_flush_on_two_devices():
    """The placement kept on four chips (PERF.md): a full 256-row
    flush at m <= 1024 is two 128-row tiles on devices 0 and 1 (the
    table's tile-128 rows), a flush of at most 128 rows one tile on
    device 0, and at m 4096 VMEM's 48-row tile deals every device."""
    with use_table(_v5e_table()):
        sched = BatchScheduler(SolverSpec(backend="kernel"),
                               devices=jax.devices()[:1] * 4)
        for bm, B, shards in [(128, 256, (128, 128, 0, 0)),
                              (1024, 200, (128, 128, 0, 0)),
                              (128, 100, (104, 0, 0, 0)),
                              (512, 5, (8, 0, 0, 0)),
                              (4096, 256, (96, 96, 96, 96))]:
            assert flush_layout(sched, bm, B).shards == shards, (bm, B)


def test_explicit_tile_wins_over_the_resolved_pin():
    """An explicit ``tile=`` is kept whatever the flush size, and the
    flush pads to whole tiles of it."""
    sched = BatchScheduler(SolverSpec(backend="kernel", tile=8),
                           devices=jax.devices()[:1] * 4)
    for B in (1, 5, 40, 256):
        pinned, b_pad = sched.flush_shape(1024, B)
        assert pinned.tile == 8
        assert b_pad % 8 == 0 and b_pad >= B


E2E = r"""
import json, sys
sys.path.insert(0, REPO)
import jax, numpy as np
from bench import check, generators
from bench.drivers import served
from repro.serve_lp import BatchScheduler, SolverSpec
from repro.serve_lp.rpc.slo import flush_layout
assert len(jax.devices()) == 4
cfg = json.load(open(REPO + "/bench/configs/served_ladder_x4.json"))
tr = json.load(open(REPO + "/bench/traffic/saturate.json"))
limits = json.load(open(REPO + "/bench/limits/served_ladder_x4.saturate.json"))
tr["m_ladder"] = [8, 64, 130, 250]          # m buckets 128 and 256
sc = cfg["scheduler"]
spec = SolverSpec(backend=cfg["backend"], M=float(cfg["M"]),
                  dtype=cfg["dtype"])
assert spec.tile is None
sched = BatchScheduler(spec, max_batch=160, max_wait_s=sc["max_wait_s"],
                       pipeline=sc["pipeline"],
                       max_inflight=sc["max_inflight"],
                       sharding=sc["sharding"], fuse=sc["fuse"])
flushes = []
record = sched.metrics.record_flush
def spy(**kw):
    predicted = flush_layout(sched, kw["bucket_m"], kw["n_real"])
    flushes.append([kw["n_real"], list(kw["shards"]),
                    list(predicted.shards)])
    record(**kw)
sched.metrics.record_flush = spy
n = 500
reqs, ms, kinds = served._requests(tr, n, np.random.default_rng([7, 0x5E]),
                                   generators.jax_key(7))
futs = [sched.submit(*r) for r in reqs]
sched.flush()
res = [f.result(timeout=600) for f in futs]
assert sched.drain(timeout=600)
sample = []
for m in sorted(set(ms.tolist())):
    idx = np.nonzero(ms == m)[0]
    sample.append({"A": np.stack([reqs[i][0] for i in idx]),
                   "b": np.stack([reqs[i][1] for i in idx]),
                   "c": np.stack([reqs[i][2] for i in idx]),
                   "m_valid": np.full(idx.size, m),
                   "x": np.stack([res[i].x for i in idx]),
                   "feasible": np.array([res[i].feasible for i in idx])})
read = check.judge(sample, cfg)
rows = sched.metrics.snapshot()["rows_per_device"]
print(json.dumps({"read": read, "limits": {k: limits[k] for k in
                  ("flags_wrong", "obj_gap")}, "flushes": flushes,
                  "rows": rows, "kinds": np.bincount(kinds).tolist()}))
"""


def test_served_ladder_on_four_devices_is_exact(multidevice):
    """The four-chip cell's scheduler settings with an unpinned kernel
    tile (interpret mode here), on seeded ladder traffic in the 80/10/10
    mix: every answer agrees with the float64 reference within the
    cell's limits with flags exact; each flush was dealt as the SLO
    controller predicts, and the per-device row counters the
    ``rows_imbalance`` reader reads add up those layouts."""
    out = multidevice(f"REPO = {str(REPO)!r}\n" + E2E, n_devices=4)
    got = json.loads(out.strip().splitlines()[-1])
    assert got["kinds"] == [400, 50, 50]
    assert got["read"]["compared"] > 0
    assert got["read"]["flags_wrong"] == 0
    assert got["read"]["obj_gap"] <= got["limits"]["obj_gap"]
    assert sum(n for n, _, _ in got["flushes"]) == 500
    for n_real, shards, predicted in got["flushes"]:
        assert shards == predicted, (n_real, shards, predicted)
    # flushes above one 128-row tile reach a second device
    assert any(sum(1 for s in shards if s) == 2
               for _, shards, _ in got["flushes"])
    assert got["rows"] == [sum(f[1][d] for f in got["flushes"])
                           for d in range(4)]


SLO_PIN = r"""
import json, time
from concurrent.futures import Future
import jax, numpy as np
from repro.serve_lp import BatchScheduler, SolverSpec
from repro.serve_lp.rpc.slo import SLOController, flush_layout
from repro.serve_lp.scheduler import _Pending
from repro.tune import (TableEntry, TableKey, TuningTable,
                        current_device_kind)
assert len(jax.devices()) == 4
kind = current_device_kind()
US = 20.0
table = TuningTable([TableEntry(
    TableKey(kind, "kernel", "float32", m_bucket=bm, batch_bucket=0),
    tile=128, chunk=0, us_per_lp=US, source="measured")
    for bm in (128, 1024)])
out = []
for bm, B in [(128, 5), (128, 17), (128, 64), (128, 100), (1024, 200),
              (1024, 256)]:
    sched = BatchScheduler(SolverSpec(backend="kernel"), max_batch=B)
    predicted = flush_layout(sched, bm, B)
    est = SLOController(10.0, table=table,
                        device_kind=kind).plan_for(sched, bm).est_flush_s
    m = bm // 2 + 1
    reqs = [_Pending(A=np.tile(np.float32([1.0, 0.0]), (m, 1)),
                     b=np.ones(m, np.float32),
                     c=np.array([1.0, 0.0], np.float32), m=m,
                     future=Future(), t_submit=time.perf_counter())
            for _ in range(B)]
    unit = sched._assemble(bm, reqs, "test")
    sched.buffers.release(unit.buf_key, unit.bufs)
    built = unit.exe.layout
    out.append({"bm": bm, "B": B, "predicted": [predicted.b_pad,
                list(predicted.shards)], "built": [unit.b_pad,
                list(built.shards)], "est": est,
                "est_built": US * 1e-6 * unit.b_pad / built.used_devices})
print(json.dumps(out))
"""


def test_slo_predicts_the_flush_the_scheduler_assembles(multidevice):
    """The SLO controller's flush model uses the scheduler's own pin: on
    four devices its predicted ``b_pad`` and layout are what
    ``_assemble`` builds, and its service estimate divides the measured
    cost by the devices that layout uses."""
    got = json.loads(multidevice(SLO_PIN, n_devices=4).strip()
                     .splitlines()[-1])
    assert len(got) == 6
    for case in got:
        assert case["predicted"] == case["built"], case
        assert case["est"] == pytest.approx(case["est_built"]), case
    full = got[-1]                        # 256 rows at m 1024
    assert full["built"] == [256, [128, 128, 0, 0]]


SPAN = r"""
import glob, json, tempfile
import jax, numpy as np
from jax.profiler import ProfileData
from repro.serve_lp import BatchScheduler, SolverSpec
assert len(jax.devices()) == 4
sched = BatchScheduler(SolverSpec(backend="kernel"), max_batch=1000,
                       fuse=False)
rng = np.random.default_rng(3)
d = tempfile.mkdtemp()
jax.profiler.start_trace(d)
try:
    for size in (5, 200):
        futs = []
        for _ in range(size):
            A = rng.normal(size=(20, 2)).astype(np.float32)
            futs.append(sched.submit(A, np.ones(20, np.float32),
                                     np.array([1.0, 0.0], np.float32)))
        sched.flush()
        [f.result(timeout=600) for f in futs]
finally:
    jax.profiler.stop_trace()
path, = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
spans = [{k: str(v) for k, v in dict(e.stats).items()}
         for plane in ProfileData.from_file(path).planes
         if plane.name.startswith("/host:") for line in plane.lines
         for e in line.events if e.name == "serve.dispatch"]
print(json.dumps(spans))
"""


def test_each_flush_dispatch_is_one_profiler_span(multidevice):
    """A profiled flush leaves exactly one ``serve.dispatch`` host event,
    naming its flush and bucket and the devices its layout uses: a 5-row
    flush one device, a 200-row flush (two 128-row tiles) two."""
    spans = json.loads(multidevice(SPAN, n_devices=4).strip()
                       .splitlines()[-1])
    assert len(spans) == 2, spans
    assert [s["chips"] for s in spans] == ["1", "2"]
    assert all(s["bucket_m"] == "128" for s in spans)
    assert len({s["flush"] for s in spans}) == 2
