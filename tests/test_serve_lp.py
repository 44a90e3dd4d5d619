"""Serving subsystem tests: bucketing math, flush policies, pipelined
dispatch/completion (overlap, backpressure, buffer-lease audit, failure
isolation), executable cache accounting, round-trip equivalence with
the direct solvers, and multi-device sharding (out-of-process)."""
import threading
import time
from concurrent.futures import Future

import jax
import numpy as np
import pytest

from repro.core import (concat_batches, make_batch, pack_call_count,
                        pad_batch_dim, ragged_feasible_lp, split_batch)
from repro.kernels import ops
from repro.solver import get_solver
from repro.serve_lp import (BatchScheduler, ExecSpec, ExecutableCache,
                            LaunchGroup, MeshLayout, ServeMetrics,
                            SolverSpec, as_executable, bucket_batch,
                            bucket_m, build_executable, plan_layout,
                            shape_ladder)
from repro.core.lp import PAD_B
from repro.obs import Tracer
from repro.serve_lp.bench import BenchConfig, make_request, run_traffic
from repro.serve_lp.metrics import DEFAULT_DURATION_BOUNDS
from repro.serve_lp.scheduler import _FlushBufferPool, _Pending

RGB8 = SolverSpec(backend="rgb", tile=8)


def _mixed_requests(seed=0, ms=(3, 8, 37, 128, 130, 200), reps=2):
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(reps):
        for m in ms:
            xstar = rng.uniform(-10, 10, 2)
            theta = rng.uniform(0, 2 * np.pi, m)
            A = np.stack([np.cos(theta), np.sin(theta)], -1)
            b = A @ xstar + rng.uniform(0.1, 3.0, m)
            phi = rng.uniform(0, 2 * np.pi)
            c = np.array([np.cos(phi), np.sin(phi)])
            reqs.append((A.astype(np.float32), b.astype(np.float32),
                         c.astype(np.float32)))
    return reqs


# -- bucketing -----------------------------------------------------------

def test_bucket_m_ladder():
    assert bucket_m(1) == 128
    assert bucket_m(128) == 128
    assert bucket_m(129) == 256
    assert bucket_m(700) == 1024
    assert bucket_m(1024) == 1024
    assert shape_ladder(1000) == [128, 256, 512, 1024]
    # dense solvers use a finer base so tiny LPs are not padded 16x
    assert bucket_m(3, base=8) == 8
    assert bucket_m(9, base=8) == 16
    assert bucket_m(130, base=8) == 256
    with pytest.raises(ValueError):
        bucket_m(0)


@pytest.mark.parametrize("backend,base", [("rgb", 8), ("naive", 8),
                                          ("kernel", 128)])
def test_scheduler_bucket_base_by_method(backend, base):
    assert BatchScheduler(SolverSpec(backend=backend)).bucket_base == base


def test_bucket_batch_ladder():
    assert bucket_batch(1, 32) == 32
    assert bucket_batch(32, 32) == 32
    assert bucket_batch(33, 32) == 64
    assert bucket_batch(100, 32) == 128


def test_exec_spec_validation():
    # only the kernel has a LANE-layout requirement
    with pytest.raises(ValueError):
        ExecSpec(bucket_m=100, b_pad=32,
                 solver=SolverSpec(backend="kernel", tile=32))
    ExecSpec(bucket_m=16, b_pad=32, solver=SolverSpec(backend="rgb",
                                                      tile=32))
    # the mesh planner owns padding: any positive b_pad is legal
    ExecSpec(bucket_m=128, b_pad=33,
             solver=SolverSpec(backend="rgb", tile=32))
    with pytest.raises(ValueError):
        ExecSpec(bucket_m=128, b_pad=0,
                 solver=SolverSpec(backend="rgb", tile=32))
    # b_pad padding needs a concrete tile (tile=None means "pick per
    # shape" on every backend now — the scheduler pins it per bucket
    # via resolve_for_shape before building an ExecSpec)
    with pytest.raises(ValueError):
        ExecSpec(bucket_m=128, b_pad=32,
                 solver=SolverSpec(backend="kernel"))
    with pytest.raises(ValueError):
        ExecSpec(bucket_m=128, b_pad=32,
                 solver=SolverSpec(backend="rgb"))
    with pytest.raises(TypeError):
        ExecSpec(bucket_m=128, b_pad=32, solver="rgb")


def test_exec_spec_keys_on_full_solver_spec():
    """Two schedulers with different solver specs must never alias
    executables: the whole SolverSpec is part of the cache key."""
    mk = lambda **kw: ExecSpec(bucket_m=16, b_pad=32,
                               solver=SolverSpec(backend="rgb", tile=32,
                                                 **kw))
    assert mk() == mk()
    assert hash(mk()) == hash(mk())
    assert mk(M=2.0e4) != mk()
    assert mk(seed=1, shuffle=True) != mk(shuffle=True)
    assert mk(normalize=False) != mk()
    # resolution canonicalises: auto==rgb on a non-TPU test backend
    if jax.default_backend() != "tpu":
        auto = ExecSpec(bucket_m=16, b_pad=32,
                        solver=SolverSpec(backend="auto", tile=32))
        assert auto == mk()


def test_scheduler_accepts_spec_and_rejects_mixed_kwargs():
    spec = SolverSpec(backend="rgb", tile=8, chunk=64)
    sched = BatchScheduler(spec, max_batch=4)
    assert sched.spec.tile == 8 and sched.spec.chunk == 64
    assert sched.batch_unit == 8
    with pytest.raises(TypeError):
        BatchScheduler("rgb")
    # tile=None stays unset on the spec (pinned per bucket at flush
    # time); the fallback padding unit is the serving default
    sched_default = BatchScheduler(SolverSpec(backend="rgb"))
    assert sched_default.spec.tile is None
    assert sched_default.batch_unit == 32
    # no spec: the rgb default
    assert BatchScheduler().spec == sched_default.spec
    # shuffle specs are rejected: the flush-wide shuffle would make a
    # request's result depend on its position in the super-batch
    with pytest.raises(ValueError, match="shuffle"):
        BatchScheduler(SolverSpec(backend="rgb", shuffle=True))


# -- core batch utilities ------------------------------------------------

def test_concat_split_roundtrip():
    b1 = ragged_feasible_lp(jax.random.key(0), 5, 20)
    b2 = ragged_feasible_lp(jax.random.key(1), 3, 50)
    fused = concat_batches([b1, b2])
    assert fused.batch == 8 and fused.m == 50
    back1, back2 = split_batch(fused, [5, 3])
    np.testing.assert_array_equal(np.asarray(back1.A[:, :20]),
                                  np.asarray(b1.A))
    np.testing.assert_array_equal(np.asarray(back2.A), np.asarray(b2.A))
    np.testing.assert_array_equal(np.asarray(back1.m_valid),
                                  np.asarray(b1.m_valid))
    # padding rows of the shorter member are neutral
    assert np.all(np.asarray(back1.A[:, 20:]) == 0.0)
    assert np.all(np.asarray(back1.b[:, 20:]) == 1.0)


def test_pad_batch_dim_neutral():
    b = ragged_feasible_lp(jax.random.key(2), 3, 10)
    p = pad_batch_dim(b, 8)
    assert p.batch == 8
    assert np.all(np.asarray(p.m_valid[3:]) == 0)
    rgb = get_solver(SolverSpec(backend="rgb", tile=32, chunk=0))
    sol = rgb.solve(p)
    direct = rgb.solve(b)
    np.testing.assert_array_equal(np.asarray(sol.x[:3]),
                                  np.asarray(direct.x))


def test_pack_constraints_bucketed():
    b = ragged_feasible_lp(jax.random.key(3), 4, 30)
    L, c, mv = ops.pack_constraints(b, m_pad=256)
    assert L.shape == (4, 4, 256)
    with pytest.raises(ValueError):
        ops.pack_constraints(b, m_pad=100)  # not a LANE multiple
    with pytest.raises(ValueError):
        ops.pack_constraints(b, m_pad=0)


# -- flush policies ------------------------------------------------------

def test_size_triggered_flush():
    sched = BatchScheduler(RGB8, max_batch=4)
    reqs = _mixed_requests(ms=(9, 10, 11, 12), reps=1)  # one bucket (16)
    futs = [sched.submit(*r) for r in reqs]
    # 4th submit hit max_batch: dispatched inline, no flush()/thread
    # needed; the completion worker resolves the futures
    for f in futs:
        f.result(timeout=60.0)
    assert sched.pending() == 0
    assert sched.metrics.flush_reasons == {"size": 1}


def test_wait_triggered_flush():
    with BatchScheduler(RGB8, max_batch=1000, max_wait_s=0.02) as sched:
        futs = [sched.submit(*r) for r in
                _mixed_requests(ms=(5, 200), reps=1)]
        deadline = time.time() + 5.0
        while not all(f.done() for f in futs):
            assert time.time() < deadline, "wait-trigger never flushed"
            time.sleep(0.01)
        assert sched.metrics.flush_reasons.get("wait", 0) >= 1


def test_manual_flush_and_pending():
    sched = BatchScheduler(RGB8, max_batch=1000)
    futs = [sched.submit(*r) for r in _mixed_requests(reps=1)]
    assert sched.pending() == len(futs)
    n = sched.flush()
    assert n == len(futs)
    assert sched.pending() == 0
    sched.drain()          # flush() dispatches; drain() is the join
    assert all(f.done() for f in futs)


# -- packed flush path ---------------------------------------------------

@pytest.mark.parametrize("method,interpret", [("rgb", None),
                                              ("kernel", True)])
def test_flush_does_zero_repacks(method, interpret):
    """The serving hot path assembles flushes directly in the packed SoA
    layout: no AoS -> SoA conversion (core.packed.pack) may run during
    submit, flush, or result scatter — on any backend."""
    sched = BatchScheduler(SolverSpec(backend=method, tile=8,
                                      interpret=interpret),
                           max_batch=1000)
    reqs = _mixed_requests(reps=2)
    n0 = pack_call_count()
    futs = [sched.submit(*r) for r in reqs]
    sched.flush()
    for f in futs:
        f.result(timeout=120.0)
    # repeat flush on warm executables: still zero
    futs = [sched.submit(*r) for r in reqs]
    sched.flush()
    for f in futs:
        f.result(timeout=120.0)
    assert pack_call_count() == n0, (
        "serve_lp flush path performed an AoS->SoA repack")


def test_flush_buffers_reused_for_stable_bucket():
    """Steady traffic on a stable bucket must not reallocate the host
    flush buffers: the per-bucket pool allocates once and every later
    flush of that shape leases the same buffers back."""
    sched = BatchScheduler(RGB8, max_batch=1000)
    reqs = _mixed_requests(ms=(9, 10, 11, 12), reps=1)  # one bucket (16)
    results = []
    for round_ in range(4):
        futs = [sched.submit(*r) for r in reqs]
        sched.flush()
        results.append([f.result(timeout=60.0) for f in futs])
    assert sched.buffers.lease_count == 4
    assert sched.buffers.alloc_count == 1, (
        "stable bucket reallocated its flush buffers "
        f"({sched.buffers.alloc_count} allocations in 4 flushes)")
    # buffer reuse must not leak state between flushes
    for later in results[1:]:
        for a, b in zip(results[0], later):
            np.testing.assert_array_equal(a.x, b.x)
            assert a.feasible == b.feasible
    # a new bucket shape allocates its own set, once
    big = _mixed_requests(ms=(200, 210), reps=1)
    for round_ in range(2):
        futs = [sched.submit(*r) for r in big]
        sched.flush()
        for f in futs:
            f.result(timeout=60.0)
    assert sched.buffers.alloc_count == 2


def _pending(ms, seed):
    """Queued requests of constraint counts ``ms``, as submit makes them."""
    return [_Pending(A=np.array(A, np.float32), b=b, c=c, m=len(b),
                     future=Future(), t_submit=time.perf_counter())
            for A, b, c in _mixed_requests(seed=seed, ms=ms, reps=1)]


@pytest.mark.parametrize("first_ms", [
    (64, 60, 63, 33, 64, 50, 64, 41),      # one bucket, m in any order
    (3, 9, 12, 20, 33, 40, 60, 64),        # a fused flush, rising m
    (64, 50, 33, 20, 12, 9, 3, 1),         # falling m
], ids=["one_bucket", "rising", "falling"])
def test_reused_flush_buffers_hold_the_neutral_background(first_ms):
    """A buffer set reused by a flush with smaller m and fewer rows than
    its last one holds exactly the neutral background (a = 0, b =
    PAD_B, c = (1, 0), m_valid = 0, bit for bit) beyond each row's m
    and beyond the flush's rows, and no new set is allocated."""
    sched = BatchScheduler(RGB8, max_batch=1000)
    first = sched._assemble(64, _pending(first_ms, 1), "manual")
    sched.buffers.release(first.buf_key, first.bufs)
    small = _pending((5, 40, 9), 2)
    unit = sched._assemble(64, small, "manual")
    assert unit.buf_key == first.buf_key
    assert unit.bufs[0] is first.bufs[0]
    assert sched.buffers.alloc_count == 1
    L, c, mv = unit.bufs
    want_L = np.zeros_like(L)
    want_L[:, 2, :] = PAD_B
    want_c = np.zeros_like(c)
    want_c[:, 0] = 1.0
    want_mv = np.zeros_like(mv)
    for i, r in enumerate(small):
        want_L[i, :2, :r.m] = r.A.T
        want_L[i, 2, :r.m] = r.b
        want_c[i] = r.c
        want_mv[i] = r.m
    for got, want in ((L, want_L), (c, want_c), (mv, want_mv)):
        assert got.tobytes() == want.tobytes()


def test_scheduler_pins_tuned_config_per_bucket():
    """A tuning-table entry matching a bucket's shape class changes the
    launch geometry of that bucket's executable; a miss keeps the
    serving default — and explicit spec values beat the table."""
    from repro.tune import (TableEntry, TableKey, TuningTable,
                            current_device_kind, use_table)
    entry = TableEntry(TableKey(current_device_kind(), "rgb", "float32",
                                m_bucket=16, batch_bucket=8), tile=8,
                       chunk=0, us_per_lp=1.0)
    req_small = _mixed_requests(ms=(9,), reps=1)[0]    # bucket_m 16
    req_large = _mixed_requests(ms=(70,), reps=1)[0]   # bucket_m 128
    with use_table(TuningTable([entry])):
        # fuse=False: this test is about *per-bucket* pinned geometry,
        # so the two buckets must flush as separate units
        sched = BatchScheduler(SolverSpec(backend="rgb"), max_batch=1000,
                               fuse=False)
        f1 = sched.submit(*req_small)
        f2 = sched.submit(*req_large)
        sched.flush()
        f1.result(timeout=60.0), f2.result(timeout=60.0)
        tiles = {k.bucket_m: k.solver.tile for k in sched.cache._cache}
        assert tiles[16] == 8, "tuned tile did not reach the ExecSpec"
        assert tiles[128] == 32, "table miss should keep the default"
        # explicit spec tile wins over the same table entry
        sched_exp = BatchScheduler(SolverSpec(backend="rgb", tile=16),
                                   max_batch=1000)
        f3 = sched_exp.submit(*req_small)
        sched_exp.flush()
        f3.result(timeout=60.0)
        assert all(k.solver.tile == 16 for k in sched_exp.cache._cache)


def test_submit_honors_spec_dtype():
    """Request buffers are assembled at the spec's dtype end-to-end
    (a float64 spec must not silently truncate through float32)."""
    sched = BatchScheduler(SolverSpec(backend="rgb", tile=8))
    req = _mixed_requests(ms=(5,), reps=1)[0]
    sched.submit(np.asarray(req[0], np.float64), req[1], req[2])
    q = next(iter(sched._queues.values()))
    assert q[0].A.dtype == np.float32 and q[0].b.dtype == np.float32
    assert q[0].c.dtype == np.float32
    sched.flush()
    if jax.config.jax_enable_x64:
        s64 = BatchScheduler(SolverSpec(backend="rgb", tile=8,
                                        dtype="float64"))
        s64.submit(*req)
        q = next(iter(s64._queues.values()))
        assert q[0].A.dtype == np.float64
        s64.flush()
    else:
        # x64 off: a float64 spec is rejected at construction, exactly
        # like the solver's own check
        with pytest.raises(ValueError, match="x64"):
            BatchScheduler(SolverSpec(backend="rgb", tile=8,
                                      dtype="float64"))


# -- round trips ---------------------------------------------------------

def test_roundtrip_bit_identical_rgb():
    """Mixed-shape requests through the scheduler give bit-identical
    results to a direct solve with the *same* SolverSpec."""
    spec = SolverSpec(backend="rgb", tile=32)
    sched = BatchScheduler(spec, max_batch=1000)
    solver = spec.build()
    reqs = _mixed_requests()
    futs = [sched.submit(*r) for r in reqs]
    sched.flush()
    for (A, b, c), f in zip(reqs, futs):
        r = f.result(timeout=60.0)
        direct = solver.solve(make_batch(A, b, c))
        assert bool(direct.feasible[0]) == r.feasible
        np.testing.assert_array_equal(np.asarray(direct.x[0]), r.x)


def test_roundtrip_kernel_interpret():
    sched = BatchScheduler(SolverSpec(backend="kernel", tile=32,
                                      interpret=True), max_batch=1000)
    reqs = _mixed_requests(ms=(5, 40), reps=2)
    futs = [sched.submit(*r) for r in reqs]
    sched.flush()
    for (A, b, c), f in zip(reqs, futs):
        r = f.result(timeout=120.0)
        direct = get_solver(SolverSpec(
            backend="kernel", interpret=True)).solve(make_batch(A, b, c))
        assert bool(direct.feasible[0]) == r.feasible
        np.testing.assert_allclose(np.asarray(direct.x[0]), r.x,
                                   rtol=1e-5, atol=1e-5)


def test_infeasible_and_degenerate_roundtrip():
    sched = BatchScheduler(RGB8, max_batch=1000)
    rng = np.random.default_rng(7)
    theta = rng.uniform(0, 2 * np.pi, 6)
    A = np.stack([np.cos(theta), np.sin(theta)], -1).astype(np.float32)
    xstar = rng.uniform(-5, 5, 2)
    degenerate = (A, (A @ xstar).astype(np.float32),
                  np.array([1.0, 0.0], np.float32))
    infeasible = (np.array([[1.0, 0.0], [-1.0, 0.0]], np.float32),
                  np.array([-1.0, -1.0], np.float32),
                  np.array([1.0, 0.0], np.float32))
    fd = sched.submit(*degenerate)
    fi = sched.submit(*infeasible)
    sched.flush()
    assert fd.result().feasible
    np.testing.assert_allclose(fd.result().x, xstar, rtol=1e-4,
                               atol=1e-4)
    assert not fi.result().feasible


# -- bulk settle: every result against a per-request reference -----------

class _RecordingExec:
    """Executable double: answers a flush with seeded ``x`` rows (wide
    magnitudes, signed zeros) and flags, and records what it was given
    and what it answered."""

    def __init__(self, log):
        self.log = log

    def dispatch(self, L, c, mv):
        rng = np.random.default_rng(len(self.log))
        n = L.shape[0]
        x = (rng.standard_normal((n, 2))
             * 10.0 ** rng.uniform(-4, 4, (n, 2))).astype(L.dtype)
        x[::5, 0] = -0.0
        feas = rng.random(n) < 0.7
        self.log.append((L.copy(), x, feas))
        return x, feas

    def complete(self, handle):
        return handle


# constraint counts of one flush each, and the futures cancelled while
# queued; fused runs fuse whole (fuse_max_m_ratio below)
_FLUSH_SHAPES = {
    "one_bucket": ((9, 10, 11, 12, 13, 14, 15, 16), ()),
    "fused_8_to_1024": ((8, 16, 32, 64, 128, 256, 512, 1024) * 2, ()),
    "fused_2048_4096": ((2048, 4096, 3000, 2500), ()),
    "one_request": ((37,), ()),
    "cancelled_while_queued": ((9, 10, 11, 12, 13, 14), (1, 4)),
}


@pytest.mark.parametrize("traced", [False, True],
                         ids=["untraced", "traced"])
@pytest.mark.parametrize("shape", sorted(_FLUSH_SHAPES))
def test_bulk_settle_matches_per_request_reference(shape, traced):
    """Every settled LPResult equals the one built request by request
    from the flush's own ``x``/``feasible`` arrays: ``x`` (bit for bit),
    ``feasible``, ``m``, ``bucket_m`` and ``batch_size`` equal, and
    ``objective`` within one float32 ulp of ``float(c @ x)``."""
    ms, cancel = _FLUSH_SHAPES[shape]
    log = []
    tracer = Tracer(enabled=True) if traced else None
    sched = BatchScheduler(RGB8, max_batch=1000, fuse_max_m_ratio=1024.0,
                           tracer=tracer)
    sched.cache = ExecutableCache(lambda s: _RecordingExec(log))
    reqs = _mixed_requests(seed=len(ms), ms=ms, reps=1)
    futs = [sched.submit(*r) for r in reqs]
    for i in cancel:
        assert futs[i].cancel()
    sched.flush()
    assert sched.drain(timeout=60.0)
    assert len(log) == 1
    L, x, feas = log[0]
    # a fused flush holds its buckets in rising m, each in submission
    # order
    live = sorted((i for i in range(len(reqs)) if i not in cancel),
                  key=lambda i: bucket_m(len(reqs[i][1]), base=8))
    for row, i in enumerate(live):
        A, b, c = reqs[i]
        m = len(b)
        # the flush's row ``row`` holds request i
        np.testing.assert_array_equal(L[row, :2, :m], A.T)
        np.testing.assert_array_equal(L[row, 2, :m], b)
        got = futs[i].result(timeout=10.0)
        assert got.x.tobytes() == x[row].tobytes()
        assert type(got.feasible) is bool
        assert got.feasible == bool(feas[row])
        assert (got.m, got.bucket_m, got.batch_size) == \
            (m, L.shape[2], len(live))
        want = float(np.asarray(c, np.float32) @ x[row])
        assert type(got.objective) is float
        np.testing.assert_array_max_ulp(np.float32(got.objective),
                                        np.float32(want), maxulp=1)
        assert got.latency_s >= 0.0
    assert all(futs[i].cancelled() for i in cancel)
    if traced:
        ends = {s.attrs.get("feasible") for s in tracer.spans()
                if s.name == "request" and "feasible" in s.attrs}
        assert ends <= {True, False} and ends
        n_cancelled = sum(1 for s in tracer.spans() if s.name == "request"
                          and s.attrs.get("cancelled"))
        assert n_cancelled == len(cancel)
    else:
        assert sched.tracer.stats()["spans_started"] == 0


# -- executable cache ----------------------------------------------------

def test_cache_hit_accounting():
    sched = BatchScheduler(RGB8, max_batch=8)
    # all in one m-bucket (16) so each round is exactly one flush
    reqs = _mixed_requests(ms=(9, 10, 11, 12, 13, 14, 15, 16), reps=1)
    for round_ in range(3):
        for r in reqs:
            sched.submit(*r)  # 8th submit size-flushes each round
    assert sched.pending() == 0
    stats = sched.cache.stats()
    # identical traffic -> one spec: 1 miss, then hits
    assert stats["misses"] == 1 and stats["size"] == 1
    assert stats["hits"] == 2
    # a new shape bucket is a new executable
    big = _mixed_requests(ms=(200,) * 8, reps=1)
    for r in big:
        sched.submit(*r)
    stats = sched.cache.stats()
    assert stats["misses"] == 2 and stats["size"] == 2
    assert stats["hit_rate"] == pytest.approx(2 / 4)


def test_bogus_method_rejected_at_construction():
    """Stringly-typed dispatch used to fail only at flush time; the
    SolverSpec validates before any scheduler is built."""
    with pytest.raises(ValueError):
        BatchScheduler(SolverSpec(backend="bogus", tile=8),
                       max_batch=1000)


def _failing_builder(spec):
    raise ValueError(f"executable build refused for {spec.bucket_m}")


def test_solver_error_propagates_to_futures():
    sched = BatchScheduler(RGB8, max_batch=1000)
    sched.cache = ExecutableCache(_failing_builder)
    f = sched.submit(*_mixed_requests(ms=(5,), reps=1)[0])
    with pytest.raises(ValueError):
        sched.flush()
    assert isinstance(f.exception(timeout=1.0), ValueError)


def test_timer_thread_survives_solver_error():
    """A failing wait-triggered flush must not kill the flush thread:
    later requests still get flushed (and their futures resolved)."""
    sched = BatchScheduler(RGB8, max_batch=1000, max_wait_s=0.01)
    sched.cache = ExecutableCache(_failing_builder)
    sched.start()
    try:
        req = _mixed_requests(ms=(5,), reps=1)[0]
        f1 = sched.submit(*req)
        assert isinstance(f1.exception(timeout=5.0), ValueError)
        f2 = sched.submit(*req)  # thread must still be alive to flush
        assert isinstance(f2.exception(timeout=5.0), ValueError)
    finally:
        sched._stop.set()
        sched._thread.join()
        sched._thread = None
    # the swallowed-and-counted timer errors are surfaced, not silent
    snap = sched.metrics.snapshot()
    assert snap["errors"].get("timer_flush", 0) >= 1
    assert "errors" in snap and "timer_flush" in \
        sched.metrics.format_report()


def _selective_failing_builder(fail_bucket_m):
    """Builder failing only for one m-bucket; others build for real."""
    def build(spec):
        if spec.bucket_m == fail_bucket_m:
            raise ValueError(f"injected failure for bucket "
                             f"{spec.bucket_m}")
        return build_executable(spec, jax.devices())
    return build


def test_multi_bucket_flush_failure_isolated():
    """One bucket's failing solve must not orphan the other buckets'
    futures: every future of the flush resolves (result or exception)
    and the first error still reaches the flush() caller.  (fuse=False:
    with fusing the same isolation holds per flush *unit* — covered by
    the fused-flush tests.)"""
    sched = BatchScheduler(RGB8, max_batch=1000, fuse=False)
    sched.cache = ExecutableCache(_selective_failing_builder(16))
    # three buckets, dict order 8 -> 16 -> 128: the failure sits in the
    # middle so both an earlier and a later bucket must survive it
    f_ok1 = sched.submit(*_mixed_requests(ms=(5,), reps=1)[0])    # 8
    f_bad = sched.submit(*_mixed_requests(ms=(9,), reps=1)[0])    # 16
    f_ok2 = sched.submit(*_mixed_requests(ms=(70,), reps=1)[0])   # 128
    with pytest.raises(ValueError, match="injected failure"):
        sched.flush()
    assert f_ok1.result(timeout=60.0).feasible in (True, False)
    assert f_ok2.result(timeout=60.0).feasible in (True, False)
    assert isinstance(f_bad.exception(timeout=60.0), ValueError)


def test_close_refuses_new_submits_and_resolves_queued():
    sched = BatchScheduler(RGB8, max_batch=1000)
    futs = [sched.submit(*r) for r in _mixed_requests(reps=1)]
    sched.close()
    for f in futs:
        assert f.result(timeout=60.0) is not None
    with pytest.raises(RuntimeError, match="closed"):
        sched.submit(*_mixed_requests(ms=(5,), reps=1)[0])
    # close is idempotent
    sched.close()


def test_close_vs_submit_race_never_orphans():
    """Hammer close() against concurrent submit(): every future handed
    out must resolve — a submit either loses the race (raises) or its
    request is caught by the final flush.  Regression test for the
    pre-fix ordering where `_closed` was set only *after* the final
    flush, so a request could enqueue with no flusher left alive."""
    req = _mixed_requests(ms=(9,), reps=1)[0]
    for _ in range(5):
        sched = BatchScheduler(RGB8, max_batch=8, max_wait_s=0.001)
        sched.start()
        futs, lock = [], threading.Lock()

        def submitter():
            while True:
                try:
                    f = sched.submit(*req)
                except RuntimeError:
                    return
                with lock:
                    futs.append(f)

        threads = [threading.Thread(target=submitter) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.02)
        sched.close()
        for t in threads:
            t.join(timeout=60.0)
            assert not t.is_alive(), "submitter never saw the close"
        for f in futs:
            f.result(timeout=60.0)   # must never hang: no orphans


# -- the pipelined serve loop --------------------------------------------


class _SlowCompleteExec:
    """Wrap a real executable so completion takes a deterministic
    minimum time — makes overlap/backpressure observable on CPU."""

    def __init__(self, inner, delay_s):
        self.inner = inner
        self.delay_s = delay_s

    def dispatch(self, L, c, mv):
        return self.inner.dispatch(L, c, mv)

    def complete(self, handle):
        time.sleep(self.delay_s)
        return self.inner.complete(handle)


class _AuditPool(_FlushBufferPool):
    """Pool that records lease/release interleaving: a buffer set may
    never be leased out twice without an intervening release."""

    def __init__(self):
        super().__init__()
        self._audit_lock = threading.Lock()
        self._out = set()
        self.max_outstanding = 0
        self.violations = 0

    def lease(self, b_pad, bm, dtype):
        key, bufs = super().lease(b_pad, bm, dtype)
        with self._audit_lock:
            bid = id(bufs[0])
            if bid in self._out:
                self.violations += 1
            self._out.add(bid)
            self.max_outstanding = max(self.max_outstanding,
                                       len(self._out))
        return key, bufs

    def release(self, key, bufs):
        with self._audit_lock:
            self._out.discard(id(bufs[0]))
        super().release(key, bufs)


def test_pipelined_overlap_backpressure_and_buffers():
    """The tentpole contract: with a slow solve, (a) >= 2 flushes are
    concurrently in flight but never more than max_inflight, (b) a
    leased buffer set is never reused while its flush is in flight,
    (c) results still scatter in submission order and match the direct
    solver."""
    spec = SolverSpec(backend="rgb", tile=8)
    sched = BatchScheduler(spec, max_batch=4, max_inflight=2)
    sched.cache = ExecutableCache(
        lambda s: _SlowCompleteExec(build_executable(s, jax.devices()),
                                    0.05))
    sched.buffers = _AuditPool()
    reqs = _mixed_requests(ms=(9, 10, 11, 12), reps=4)  # one bucket (16)
    futs = [sched.submit(*r) for r in reqs]             # 4 size flushes
    results = [f.result(timeout=120.0) for f in futs]
    sched.drain()
    snap = sched.metrics.snapshot()
    # (a) overlap happened and the depth bound held
    assert snap["inflight_max"] == 2, snap
    assert snap["overlapped_dispatches"] >= 1
    assert snap["inflight_now"] == 0
    assert snap["n_dispatched"] == 4
    # the device-idle estimate exists and is bounded by the elapsed time
    assert 0.0 <= snap["device_idle_s_est"] <= snap["elapsed_s"] + 1.0
    # (b) the buffer audit: concurrent flushes used disjoint buffer
    # sets (>= 2 live at once), never a leased one
    assert sched.buffers.violations == 0
    assert sched.buffers.max_outstanding >= 2
    assert sched.buffers.lease_count == 4
    # assembly overlapping in-flight solves => more than one set was
    # allocated, but backpressure bounds it to max_inflight + 1
    assert 2 <= sched.buffers.alloc_count <= 3
    # (c) submission-order scatter, bit-identical to the direct solver
    solver = spec.build()
    for (A, b, c), r in zip(reqs, results):
        direct = solver.solve(make_batch(A, b, c))
        assert bool(direct.feasible[0]) == r.feasible
        np.testing.assert_array_equal(np.asarray(direct.x[0]), r.x)


def test_pipelined_solve_failure_reaches_futures_not_flush_caller():
    """A failure surfacing at completion (after dispatch) lands on the
    flush's futures and the error counter — flush() itself already
    returned."""
    class _FailingComplete:
        def dispatch(self, L, c, mv):
            return "handle"

        def complete(self, handle):
            raise RuntimeError("injected completion failure")

    sched = BatchScheduler(RGB8, max_batch=1000)
    sched.cache = ExecutableCache(lambda s: _FailingComplete())
    f = sched.submit(*_mixed_requests(ms=(5,), reps=1)[0])
    sched.flush()          # dispatch succeeds; no raise here
    assert isinstance(f.exception(timeout=60.0), RuntimeError)
    sched.drain()
    assert sched.metrics.snapshot()["errors"].get("solve", 0) == 1


def test_as_executable_adapts_plain_callables():
    calls = []

    def sync_fn(L, c, mv):
        calls.append(L.shape)
        return "x", "feas"

    exe = as_executable(sync_fn)
    assert exe.complete(exe.dispatch(np.zeros((2, 4, 8)), None, None)) \
        == ("x", "feas")
    assert calls == [(2, 4, 8)]
    # real executables and test doubles pass through unchanged
    assert as_executable(exe) is exe


# -- metrics -------------------------------------------------------------

def test_metrics_percentiles():
    m = ServeMetrics()
    for v in range(1, 101):
        m.record_latency(v / 1000.0)
    assert m.percentile(50) == pytest.approx(0.0505)
    assert m.percentile(99) == pytest.approx(0.09901)
    m.record_flush(n_real=3, b_pad=8, bucket_m=128, sum_m=30,
                   solve_seconds=0.01, reason="manual")
    s = m.snapshot()
    assert s["padding_waste_problems"] == pytest.approx(5 / 8)
    assert s["padding_waste_cells"] == pytest.approx(1 - 30 / (8 * 128))


def test_latency_reservoir_stays_uniform():
    """Past capacity the reservoir keeps sampling (deterministically,
    no `random` on the hot path) instead of freezing on the first k
    samples — late-run latencies must stay represented."""
    m = ServeMetrics(max_latency_samples=100)
    n = 5000
    for v in range(n):
        m.record_latency(float(v))
    s = m.snapshot()
    assert s["latency_seen"] == n
    assert s["latency_samples"] == 100
    kept = sorted(m._latencies)
    # uniform reservoir: the second half of the run is represented
    # (a capped list would hold only 0..99, median would be ~50)
    assert sum(1 for v in kept if v >= n / 2) >= 20
    assert m.percentile(50.0) > n * 0.2
    # deterministic: same stream -> same reservoir
    m2 = ServeMetrics(max_latency_samples=100)
    for v in range(n):
        m2.record_latency(float(v))
    assert m2._latencies == m._latencies
    # the report names the sampling so percentiles aren't over-read
    assert "reservoir: 100 of 5000" in m.format_report()


@pytest.mark.parametrize("family", ["request_latency_seconds",
                                    "queue_wait_seconds"])
def test_batch_recording_matches_one_by_one(family):
    """The batch forms (``record_latencies``, ``record_queue_waits``)
    give the same histogram counts, sums, exemplars and, for latencies,
    the same reservoir as the samples sent one by one — past the
    reservoir's capacity too."""
    rng = np.random.default_rng(11)
    vals = np.concatenate([10.0 ** rng.uniform(-5, 3, 400),
                           DEFAULT_DURATION_BOUNDS[::4]])  # on bounds
    rng.shuffle(vals)
    tids = [f"{i:032x}" if i % 3 == 0 else None for i in range(len(vals))]
    one = ServeMetrics(max_latency_samples=64)
    bulk = ServeMetrics(max_latency_samples=64)
    if family == "request_latency_seconds":
        single, batch = one.record_latency, bulk.record_latencies
    else:
        single, batch = one.record_queue_wait, bulk.record_queue_waits
    for v, t in zip(vals.tolist(), tids):
        single(v, trace_id=t)
    cuts = [0, 1, 9, 60, 61, 200, len(vals)]
    for s, e in zip(cuts, cuts[1:]):
        batch(vals[s:e], tids[s:e])
    assert bulk.hists[family].state() == one.hists[family].state()
    assert bulk.hists[family].count == len(vals)
    assert bulk.lat_seen == one.lat_seen
    assert bulk._latencies == one._latencies
    assert bulk._lat_rng == one._lat_rng


def test_error_counter_and_one_time_warning():
    m = ServeMetrics()
    with pytest.warns(RuntimeWarning, match="broken thing"):
        m.record_error("timer_flush", warn="broken thing happened")
    # second error of the same kind counts but does not warn again
    m.record_error("timer_flush", warn="broken thing happened")
    m.record_error("solve")
    s = m.snapshot()
    assert s["errors"] == {"timer_flush": 2, "solve": 1}
    assert "timer_flush=2" in m.format_report()


def test_bench_traffic_deterministic():
    a = make_request(BenchConfig(seed=3), 5)
    b = make_request(BenchConfig(seed=3), 5)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert a[3] == b[3]


def test_bench_smoke_tiny():
    cfg = BenchConfig(requests=24, rate=1e6, m_max=64, max_batch=8,
                      max_wait_s=0.005, tile=8, check=3, warmup=False)
    snap, sched = run_traffic(cfg, quiet=True)
    assert snap["n_solved"] == 24
    assert snap["cache"]["misses"] >= 1
    assert 0.0 <= snap["padding_waste_cells"] < 1.0
    assert np.isfinite(snap["latency_p99_ms"])
    # pipelined loop fully quiesced, every dispatch completed
    assert snap["inflight_now"] == 0
    assert snap["n_dispatched"] == snap["n_flushes"]
    assert snap["errors"] == {}


# -- mesh layout planning (pure layout algebra, no devices needed) -------

def test_plan_layout_even_split():
    lay = plan_layout(64, 16, 4)
    assert lay.shards == (16, 16, 16, 16)
    assert lay.b_pad == 64 and lay.used_devices == 4
    (g,) = lay.groups
    assert g.sizes == (4, 16) and g.strides == (16, 1)
    assert g.offset == 0 and lay.n_launches == 1


def test_plan_layout_uneven_two_groups():
    # 5 tiles dealt over 4 devices: q/q+1 with the larger shard first,
    # so the launch plan is exactly two contiguous groups
    lay = plan_layout(80, 16, 4)
    assert lay.shards == (32, 16, 16, 16)
    g0, g1 = lay.groups
    assert g0 == LaunchGroup(start=0, n_devices=1, rows_per_device=32,
                             offset=0)
    assert g1 == LaunchGroup(start=1, n_devices=3, rows_per_device=16,
                             offset=32)
    # the layout algebra maps (device, local row) -> global row
    assert lay.global_row(0, 31) == 31
    assert lay.global_row(1, 0) == 32
    assert lay.global_row(3, 15) == 79
    with pytest.raises(IndexError):
        lay.global_row(1, 16)


def test_plan_layout_underfull_skips_devices():
    # an underfull flush simply doesn't use trailing devices (an even
    # split would instead pad the batch 4x to occupy them)
    lay = plan_layout(16, 16, 4)
    assert lay.shards == (16, 0, 0, 0)
    assert lay.used_devices == 1 and lay.n_launches == 1
    assert lay.groups[0].rows == 16


def test_plan_layout_prime_rows_pad_to_tile_only():
    # the planner owns padding: prime row counts round up to whole
    # kernel tiles, never to tile * n_devices blocks
    lay = plan_layout(37, 16, 4)
    assert lay.b_pad == 48 and lay.shards == (16, 16, 16, 0)
    assert lay.pad_rows(37) == 11
    assert "48 rows = [16 16 16 0] @ tile=16, 1 launch" == lay.describe()


def test_plan_layout_invariants_sweep():
    # every (rows, devices) combination: padding bounded by one tile,
    # at most two launches, groups cover the padded batch exactly
    for rows in range(1, 161, 7):
        for n_dev in (1, 2, 3, 4, 5, 8):
            lay = plan_layout(rows, 8, n_dev)
            assert rows <= lay.b_pad < rows + 8
            assert lay.n_launches <= 2
            assert sum(g.rows for g in lay.groups) == lay.b_pad
            assert lay.offsets[0] == 0
            for g in lay.groups:
                assert g.rows_per_device % 8 == 0


def test_plan_layout_and_mesh_layout_validation():
    with pytest.raises(ValueError):
        plan_layout(0, 16, 4)
    with pytest.raises(ValueError):
        plan_layout(16, 0, 4)
    with pytest.raises(ValueError):
        plan_layout(16, 16, 0)
    with pytest.raises(ValueError):
        MeshLayout(shards=(15,), tile=16)   # not a tile multiple
    with pytest.raises(ValueError):
        MeshLayout(shards=(0, 0), tile=16)  # carries zero rows
    with pytest.raises(ValueError):
        MeshLayout(shards=(), tile=16)


# -- cross-bucket fused flush units --------------------------------------

def _direct_solve(spec, A, b, c):
    return spec.build().solve(make_batch(A, b, c))


def test_fused_flush_scatter_routing():
    """A manual flush over several underfull buckets fuses them into
    shared launches; every request's result still lands on its own
    future, bit-identical to a direct solve."""
    spec = SolverSpec(backend="rgb", tile=8)
    sched = BatchScheduler(spec, max_batch=64, max_wait_s=60.0)
    assert sched.fuse   # mesh sharding fuses by default
    reqs = _mixed_requests(ms=(3, 5, 12, 14, 30, 60), reps=2)
    futs = [sched.submit(*r) for r in reqs]
    sched.flush()
    results = [f.result(timeout=120.0) for f in futs]
    sched.drain()
    for (A, b, c), r in zip(reqs, results):
        d = _direct_solve(spec, A, b, c)
        assert bool(d.feasible[0]) == r.feasible
        np.testing.assert_array_equal(np.asarray(d.x[0]), r.x)
    snap = sched.metrics.snapshot()
    # buckets 8/16/32/64 fused into one unit (m spread 8 <= ratio)
    assert snap["flush_reasons"] == {"fused": 1}
    assert snap["fused_flushes"] == 1
    assert snap["fused_buckets"] == 4
    assert snap["launches_total"] >= 1
    sched.close()


def test_fused_joint_fill_submit_trigger():
    """Buckets that are individually under max_batch but jointly fill a
    launch flush at submit time — no wait, no manual flush."""
    spec = SolverSpec(backend="rgb", tile=8)
    sched = BatchScheduler(spec, max_batch=8, max_wait_s=60.0)
    reqs = (_mixed_requests(ms=(5,), reps=4)
            + _mixed_requests(seed=1, ms=(12,), reps=4))
    futs = [sched.submit(*r) for r in reqs]
    # the 8th submit crossed the joint-fill threshold: results arrive
    # without any flush() call or wait-trigger tick
    results = [f.result(timeout=120.0) for f in futs]
    sched.drain()
    for (A, b, c), r in zip(reqs, results):
        d = _direct_solve(spec, A, b, c)
        assert bool(d.feasible[0]) == r.feasible
        np.testing.assert_array_equal(np.asarray(d.x[0]), r.x)
    snap = sched.metrics.snapshot()
    assert snap["flush_reasons"].get("fused") == 1
    assert snap["fused_buckets"] == 2
    assert sched.pending() == 0
    sched.close()


def test_fuse_respects_m_ratio_and_disable():
    """Buckets whose m_pad spread exceeds fuse_max_m_ratio never share
    a unit, and fuse=False restores strict per-bucket flushes."""
    spec = SolverSpec(backend="rgb", tile=8)
    sched = BatchScheduler(spec, max_batch=64, max_wait_s=60.0,
                           fuse_max_m_ratio=2.0)
    futs = [sched.submit(*r) for r in
            _mixed_requests(ms=(5, 12, 100), reps=1)]  # buckets 8,16,128
    sched.flush()
    for f in futs:
        f.result(timeout=120.0)
    sched.drain()
    snap = sched.metrics.snapshot()
    # 8 and 16 fuse (ratio 2), 128 flushes alone
    assert snap["fused_flushes"] == 1 and snap["fused_buckets"] == 2
    assert snap["n_flushes"] == 2
    sched.close()

    nofuse = BatchScheduler(spec, max_batch=64, max_wait_s=60.0,
                            fuse=False)
    futs = [nofuse.submit(*r) for r in
            _mixed_requests(ms=(5, 12, 30), reps=1)]
    nofuse.flush()
    for f in futs:
        f.result(timeout=120.0)
    nofuse.drain()
    snap = nofuse.metrics.snapshot()
    assert snap["fused_flushes"] == 0
    assert snap["n_flushes"] == 3
    assert snap["flush_reasons"] == {"manual": 3}
    nofuse.close()


def test_fused_policy_allow_fuse_veto():
    """A 3-tuple bucket policy's allow_fuse=False keeps that bucket out
    of fused units while others still fuse."""
    spec = SolverSpec(backend="rgb", tile=8)
    sched = BatchScheduler(spec, max_batch=64, max_wait_s=60.0)
    sched.set_bucket_policy(
        lambda bm: (64, 60.0, bm != 8))   # bucket 8 must fly solo
    futs = [sched.submit(*r) for r in
            _mixed_requests(ms=(5, 12, 30), reps=1)]
    sched.flush()
    for f in futs:
        f.result(timeout=120.0)
    sched.drain()
    snap = sched.metrics.snapshot()
    # 16 + 32 fused; 8 flushed alone despite being fusable by ratio
    assert snap["n_flushes"] == 2
    assert snap["fused_flushes"] == 1 and snap["fused_buckets"] == 2
    sched.close()


def test_fused_flush_buffer_pool_audit():
    """Fused units lease/release flush buffers with the same
    no-double-lease discipline as plain flushes."""
    spec = SolverSpec(backend="rgb", tile=8)
    sched = BatchScheduler(spec, max_batch=16, max_wait_s=60.0)
    sched.buffers = _AuditPool()
    futs = []
    for rep in range(3):
        futs += [sched.submit(*r) for r in
                 _mixed_requests(seed=rep, ms=(3, 5, 12, 14), reps=2)]
        sched.flush()
    for f in futs:
        f.result(timeout=120.0)
    sched.drain()
    assert sched.buffers.violations == 0
    assert sched.buffers.lease_count == \
        sched.metrics.snapshot()["n_flushes"]
    assert sched.metrics.snapshot()["fused_flushes"] >= 1
    sched.close()


@pytest.mark.parametrize("kwargs,exc", [
    ({"sharding": "pmap"}, ValueError),
    ({"sharding": "banana"}, ValueError),
    ({"pipeline": False}, ValueError),
    ({"method": "rgb"}, TypeError),
    ({"tile": 8}, TypeError),
], ids=["sharding-pmap", "sharding-banana", "pipeline-off", "method",
        "tile"])
def test_retired_scheduler_modes_are_refused(kwargs, exc):
    """One serve loop, one sharding path, one way to name the solver:
    the retired modes and the flag-bag kwargs are refused at
    construction, and the values the benchmark passes are accepted."""
    with pytest.raises(exc):
        BatchScheduler(RGB8, **kwargs)
    assert BatchScheduler(RGB8, pipeline=True, sharding="mesh").fuse


def _queues(rows):
    # The fuse rule reads only a bucket's request count.
    return {bm: [None] * n for bm, n in rows.items()}


def _shape(units):
    return [[(bm, len(q)) for bm, q in unit] for unit in units]


@pytest.mark.parametrize("trigger,rows,units,left", [
    # joint fill: buckets 8/16/32 hold 8 rows together
    ("joint", {8: 3, 16: 3, 32: 2}, [[(8, 3), (16, 3), (32, 2)]], []),
    # a joint fill takes its whole run, even past max_batch rows
    ("joint", {8: 5, 16: 6}, [[(8, 5), (16, 6)]], []),
    # the m ratio splits 8/16 from 256/512; the run with more rows goes
    ("joint", {8: 2, 16: 2, 256: 3, 512: 5},
     [[(256, 3), (512, 5)]], [8, 16]),
    # equal runs: the first (smallest m) goes
    ("joint", {8: 4, 16: 4, 256: 4, 512: 4}, [[(8, 4), (16, 4)]],
     [256, 512]),
    # no run of two buckets: nothing fires
    ("joint", {8: 3, 128: 6}, [], [8, 128]),
    # a manual flush caps each unit at max_batch rows
    ("drain", {8: 3, 16: 3, 32: 3, 64: 2},
     [[(8, 3), (16, 3)], [(32, 3), (64, 2)]], None),
    # a full bucket flies alone; the rest fuse
    ("drain", {8: 8, 16: 2, 32: 1}, [[(8, 8)], [(16, 2), (32, 1)]], None),
    # the m ratio splits a drain too
    ("drain", {8: 1, 16: 1, 128: 1, 256: 1},
     [[(8, 1), (16, 1)], [(128, 1), (256, 1)]], None),
], ids=["joint-three", "joint-past-cap", "joint-ratio", "joint-tie",
        "joint-none", "drain-cap", "drain-full-single", "drain-ratio"])
def test_fuse_triggers_share_one_rule(trigger, rows, units, left):
    """The joint-fill trigger (submit path) and the drain planner
    (manual and wait flushes) group buckets by one rule: sorted by
    m_pad, split where m_pad passes the run's first times
    fuse_max_m_ratio; the planner also splits at max_batch rows."""
    sched = BatchScheduler(RGB8, max_batch=8)
    sched._queues = _queues(rows)
    if trigger == "joint":
        fused = sched._pop_fused_locked()
        got = [] if fused is None else [fused]
        assert sorted(sched._queues) == left
    else:
        got = sched._plan_units(list(sched._queues.items()))
    assert _shape(got) == units


# -- multi-device sharding (out-of-process, forced host devices) ---------

def test_sharded_matches_single_device(multidevice):
    code = """
import jax, numpy as np
from repro.core import make_batch
from repro.solver import SolverSpec, get_solver
from repro.serve_lp import BatchScheduler
assert len(jax.devices()) == 4
rng = np.random.default_rng(0)
reqs = []
for m in (3, 8, 40, 130) * 4:
    theta = rng.uniform(0, 2 * np.pi, m)
    A = np.stack([np.cos(theta), np.sin(theta)], -1).astype(np.float32)
    b = (A @ rng.uniform(-5, 5, 2) + rng.uniform(0.1, 2, m)).astype(
        np.float32)
    c = np.array([1.0, 0.5], np.float32)
    reqs.append((A, b, c))
sched = BatchScheduler(SolverSpec(backend="rgb", tile=8), max_batch=1000)
futs = [sched.submit(*r) for r in reqs]
sched.flush()
for (A, b, c), f in zip(reqs, futs):
    r = f.result(timeout=60.0)
    d = get_solver(SolverSpec(backend="rgb", tile=8,
                              chunk=0)).solve(make_batch(A, b, c))
    assert bool(d.feasible[0]) == r.feasible
    np.testing.assert_allclose(np.asarray(d.x[0]), r.x, rtol=1e-5,
                               atol=1e-5)
print("sharded-ok", len(reqs))
"""
    out = multidevice(code, n_devices=4)
    assert "sharded-ok 16" in out


def test_mesh_vs_pmap_bit_identity(multidevice):
    """The mesh path's equivalence claim: over an adversarial packed
    batch (ragged + infeasible + degenerate rows), the shard_map mesh
    path on 4 devices, a plain single-launch jit on one device and the
    unsharded solve produce bit-identical results."""
    code = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.core import concat_batches, make_batch, ragged_feasible_lp
from repro.core.packed import pack
from repro.serve_lp import ExecSpec, SolverSpec, build_executable
from repro.solver import solve_with_spec
assert len(jax.devices()) == 4
rng = np.random.default_rng(7)
batches = [ragged_feasible_lp(jax.random.key(0), 20, 24, m_min=2)]
# infeasible rows: two opposed halfplanes
A = np.array([[1.0, 0.0], [-1.0, 0.0], [0.0, 1.0]], np.float32)
b = np.array([-1.0, -1.0, 5.0], np.float32)
batches.append(make_batch(A, b, np.array([1.0, 0.5], np.float32)))
# degenerate: every constraint tight at one point
th = rng.uniform(0, 2 * np.pi, 24).astype(np.float32)
Ad = np.stack([np.cos(th), np.sin(th)], -1).astype(np.float32)
x0 = rng.uniform(-5, 5, 2).astype(np.float32)
batches.append(make_batch(Ad, Ad @ x0, np.array([0.0, 1.0], np.float32)))
lp = concat_batches(batches)
pb = pack(lp, m_pad=32)
B = int(pb.L.shape[0])
assert B == 22
# pad to 64 rows (16 per device) so all three paths take identical input
pad = 64 - B
Lp = np.zeros((pad, 4, 32), np.float32); Lp[:, 2, :] = 1e9
L = np.concatenate([np.asarray(pb.L), Lp])
c = np.concatenate([np.asarray(pb.c),
                    np.tile(np.array([[1.0, 0.0]], np.float32),
                            (pad, 1))])
mv = np.concatenate([np.asarray(pb.m_valid),
                     np.zeros((pad, 1), np.int32)])
solver = SolverSpec(backend="rgb", tile=16)
mesh_exe = build_executable(
    ExecSpec(bucket_m=32, b_pad=64, solver=solver, n_devices=4),
    jax.devices())
single_exe = build_executable(
    ExecSpec(bucket_m=32, b_pad=64, solver=solver, n_devices=1),
    jax.devices()[:1])
assert mesh_exe.shards == (16, 16, 16, 16)
assert single_exe.shards == (64,) and single_exe.n_launches == 1
xm, fm = mesh_exe(L, c, mv)
xs, fs = single_exe(L, c, mv)
from repro.core.packed import PackedLPBatch
ref = solve_with_spec(dataclasses.replace(solver),
                      PackedLPBatch(L=jnp.asarray(L), c=jnp.asarray(c),
                                    m_valid=jnp.asarray(mv)))
np.testing.assert_array_equal(xm, xs)
np.testing.assert_array_equal(fm, fs)
np.testing.assert_array_equal(xm, np.asarray(ref.x))
np.testing.assert_array_equal(fm, np.asarray(ref.feasible))
assert fm[:B].sum() == 21   # the one infeasible row stayed infeasible
print("identity-ok", B)
"""
    out = multidevice(code, n_devices=4)
    assert "identity-ok 22" in out


def test_uneven_shards_match_reference(multidevice):
    """5 tiles over 4 devices: a two-group uneven layout (32+16+16+16)
    solves to exactly what a single plain-jit launch produces."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import ragged_feasible_lp
from repro.core.packed import pack
from repro.serve_lp import ExecSpec, SolverSpec, build_executable
from repro.solver import solve_with_spec
assert len(jax.devices()) == 4
lp = ragged_feasible_lp(jax.random.key(5), 80, 24, m_min=2)
pb = pack(lp, m_pad=32)
L, c, mv = (np.asarray(pb.L), np.asarray(pb.c), np.asarray(pb.m_valid))
solver = SolverSpec(backend="rgb", tile=16)
exe = build_executable(
    ExecSpec(bucket_m=32, b_pad=80, solver=solver, n_devices=4),
    jax.devices())
assert exe.layout.shards == (32, 16, 16, 16)
assert exe.n_launches == 2
x, feas = exe(L, c, mv)
from repro.core.packed import PackedLPBatch
ref = solve_with_spec(solver, PackedLPBatch(
    L=jnp.asarray(L), c=jnp.asarray(c), m_valid=jnp.asarray(mv)))
np.testing.assert_array_equal(x, np.asarray(ref.x))
np.testing.assert_array_equal(feas, np.asarray(ref.feasible))
assert feas.all() and x.shape == (80, 2)
print("uneven-ok", exe.layout.describe())
"""
    out = multidevice(code, n_devices=4)
    assert "uneven-ok 80 rows = [32 16 16 16] @ tile=16, 2 launches" \
        in out


def test_prime_sized_flush_on_four_devices(multidevice):
    """Regression for the silent whole-shard requirement: a prime-sized
    flush (b_pad=37) on 4 devices builds, pads to whole tiles inside
    the executable, and returns exactly 37 trimmed rows."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import ragged_feasible_lp
from repro.core.packed import pack
from repro.serve_lp import ExecSpec, SolverSpec, build_executable
from repro.solver import solve_with_spec
assert len(jax.devices()) == 4
lp = ragged_feasible_lp(jax.random.key(11), 37, 24, m_min=2)
pb = pack(lp, m_pad=32)
L, c, mv = (np.asarray(pb.L), np.asarray(pb.c), np.asarray(pb.m_valid))
solver = SolverSpec(backend="rgb", tile=16)
exe = build_executable(
    ExecSpec(bucket_m=32, b_pad=37, solver=solver, n_devices=4),
    jax.devices())
# ceil(37/16)=3 tiles: one per device, fourth device unused, 11 pad
# rows -- not the 27 pad rows a whole 16*4 block would cost
assert exe.layout.shards == (16, 16, 16, 0)
assert exe.layout.pad_rows(37) == 11
x, feas = exe(L, c, mv)
assert x.shape == (37, 2) and feas.shape == (37,)
from repro.core.packed import PackedLPBatch
ref = solve_with_spec(solver, PackedLPBatch(
    L=jnp.asarray(L), c=jnp.asarray(c), m_valid=jnp.asarray(mv)))
np.testing.assert_array_equal(x, np.asarray(ref.x))
np.testing.assert_array_equal(feas, np.asarray(ref.feasible))
assert feas.all()
print("prime-ok", int(feas.sum()))
"""
    out = multidevice(code, n_devices=4)
    assert "prime-ok 37" in out


def test_fused_scheduler_multidevice(multidevice):
    """End-to-end fused serving on a real 4-device mesh: heterogeneous
    underfull buckets fuse into shared launches, results stay
    bit-identical to direct solves, and unused devices carry no rows."""
    code = """
import jax, numpy as np
from repro.core import make_batch
from repro.serve_lp import BatchScheduler, SolverSpec
from repro.solver import get_solver
assert len(jax.devices()) == 4
spec = SolverSpec(backend="rgb", tile=8)
sched = BatchScheduler(spec, max_batch=64, max_wait_s=60.0)
rng = np.random.default_rng(2)
reqs = []
for m in (3, 5, 12, 14, 30, 60) * 2:
    theta = rng.uniform(0, 2 * np.pi, m)
    A = np.stack([np.cos(theta), np.sin(theta)], -1).astype(np.float32)
    b = (A @ rng.uniform(-5, 5, 2) + rng.uniform(0.1, 2, m)).astype(
        np.float32)
    reqs.append((A, b, np.array([1.0, 0.5], np.float32)))
futs = [sched.submit(*r) for r in reqs]
sched.flush()
solver = get_solver(spec)
for (A, b, c), f in zip(reqs, futs):
    r = f.result(timeout=120.0)
    d = solver.solve(make_batch(A, b, c))
    assert bool(d.feasible[0]) == r.feasible
    np.testing.assert_array_equal(np.asarray(d.x[0]), r.x)
sched.drain()
snap = sched.metrics.snapshot()
assert snap["fused_flushes"] == 1 and snap["fused_buckets"] == 4
assert snap["launches_total"] >= 1
assert len(snap["rows_per_device"]) == 4
# 12 fused reqs pad to b_pad=16 (two 8-row tiles), spread over two
# devices; the other two devices carry no rows
assert sum(snap["rows_per_device"]) == 16
assert snap["rows_per_device"].count(0) == 2
print("fused-mesh-ok", snap["rows_per_device"])
"""
    out = multidevice(code, n_devices=4)
    assert "fused-mesh-ok" in out
