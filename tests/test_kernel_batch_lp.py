"""Pallas RGB kernel validation: shape/dtype sweeps in interpret mode
against the pure-jnp oracle (kernels.ref) and scipy, bit for bit against
the kernel's earlier one-step-per-constraint loop, and its re-solve
counter against a numpy Seidel solve."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from _kernel_reference import reference_rgb_pallas
from _pad_reference import scatter_pad_packed, scatter_pad_packed_batch_dim
from jax.experimental import pallas as pl

from repro.core import (adversarial_lp, concat_batches,
                        infeasible_lp, make_batch, normalize_batch, oneD,
                        ragged_feasible_lp, random_feasible_lp,
                        shuffle_batch)
from repro.kernels import ops, ref
from repro.kernels.batch_lp import (VMEM_BUDGET_BYTES, _WORDS_PER_LANE,
                                    _pick_tile, rgb_pallas)
import repro.solver.solver as solver_mod
from repro.solver import (DEFAULT_M, SolverSpec, get_solver,
                          solve_kernel_with_stats, solve_with_spec)


def solve_rgb(lp):
    """Reference rgb solve at the historical defaults (tile 32, dense
    re-solve); normalisation already applied by the caller."""
    return get_solver(SolverSpec(backend="rgb", tile=32, chunk=0,
                                 normalize=False)).solve(lp)


def solve_kernel(lp, tile=None):
    """Interpret-mode kernel solve (tile auto unless pinned)."""
    return get_solver(SolverSpec(backend="kernel", tile=tile,
                                 normalize=False,
                                 interpret=True)).solve(lp)


@pytest.mark.parametrize("batch,m", [
    (8, 5), (64, 37), (100, 200), (3, 1), (128, 128), (17, 513),
])
def test_kernel_matches_ref(batch, m):
    lp = random_feasible_lp(jax.random.key(batch + m), batch, m)
    nb = shuffle_batch(jax.random.key(1), normalize_batch(lp))
    r = solve_rgb(nb)
    k = solve_kernel(nb)
    np.testing.assert_array_equal(np.asarray(r.feasible),
                                  np.asarray(k.feasible))
    np.testing.assert_allclose(np.asarray(r.x), np.asarray(k.x),
                               rtol=1e-4, atol=1e-4)


def test_kernel_packed_interface_matches_ref():
    lp = normalize_batch(random_feasible_lp(jax.random.key(0), 32, 50))
    L, c, mv = ops.pack_constraints(lp)
    x_ref, feas_ref = ref.solve_packed_ref(L, c, mv)
    sol = solve_kernel(lp)
    np.testing.assert_allclose(np.asarray(sol.x), np.asarray(x_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(sol.feasible).astype(np.int32), np.asarray(feas_ref))


def test_kernel_infeasible():
    lp = normalize_batch(infeasible_lp(16, 20))
    sol = solve_kernel(lp)
    assert not bool(jnp.any(sol.feasible))


def test_kernel_ragged():
    lp = shuffle_batch(jax.random.key(7), normalize_batch(
        ragged_feasible_lp(jax.random.key(6), 40, 70)))
    r = solve_rgb(lp)
    k = solve_kernel(lp)
    np.testing.assert_allclose(np.asarray(r.x), np.asarray(k.x),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m", [64, 128])
def test_kernel_solve_same_as_scatter_padding(m, monkeypatch):
    # m 64 pads to 128 lanes, m 128 does not; batch 20 pads to tile 8
    # either way.  The solve must not move when the padding is written
    # by the pad's own pass instead of a scatter afterwards.
    lp = shuffle_batch(jax.random.key(m + 1),
                       ragged_feasible_lp(jax.random.key(m), 20, m))
    spec = SolverSpec(backend="kernel", tile=8, interpret=True)

    def solve():
        return jax.jit(lambda b: solve_with_spec(spec, b))(lp)

    got = solve()
    monkeypatch.setattr(solver_mod, "pad_packed", scatter_pad_packed)
    monkeypatch.setattr(solver_mod, "pad_packed_batch_dim",
                        scatter_pad_packed_batch_dim)
    want = solve()
    assert np.asarray(want.feasible).any()
    for field in ("x", "feasible", "objective"):
        g = np.asarray(getattr(got, field))
        w = np.asarray(getattr(want, field))
        assert np.array_equal(g.view(np.uint8), w.view(np.uint8)), field


@pytest.mark.parametrize("tile", [8, 32, 128])
def test_kernel_tile_sizes(tile):
    lp = normalize_batch(random_feasible_lp(jax.random.key(2), 48, 30))
    base = solve_kernel(lp)
    t = solve_kernel(lp, tile=tile)
    np.testing.assert_allclose(np.asarray(base.x), np.asarray(t.x),
                               rtol=1e-5, atol=1e-5)


def test_pick_tile_vmem_budget():
    # The modelled working set (the double-buffered (4, m_pad) block
    # plus the dense re-solve's (T, m_pad) temporaries, 14 words per
    # lane) must stay within the scoped-VMEM budget at every itemsize
    for itemsize in (4, 8):
        for m_pad in (128, 1024, 8192, 65536):
            t = _pick_tile(m_pad, itemsize=itemsize)
            assert t >= 8 and t % 8 == 0
            working_set = t * _WORDS_PER_LANE * m_pad * itemsize
            assert working_set <= VMEM_BUDGET_BYTES or t == 8


def test_pick_tile_pinned():
    # Pin chosen tiles for representative (B, m_pad) pairs so VMEM-model
    # changes are deliberate, not accidental.  The model counts 14 words
    # per constraint lane per problem (2 x 4 for the double-buffered
    # input block, 6 for the dense re-solve's temporaries) against a
    # 12 MiB budget under v5e's 16 MiB scoped-VMEM limit.
    assert _pick_tile(128) == 128
    assert _pick_tile(512) == 128
    assert _pick_tile(8192) == 24
    assert _pick_tile(65536) == 8      # floor: minimum viable tile
    # batch clamp: small batches get small tiles (multiple of 8 >= B)
    assert _pick_tile(128, 20) == 24
    assert _pick_tile(128, 4) == 8
    assert _pick_tile(128, 1000) == 128
    assert _pick_tile(4096, 40) == 40
    # float64 working sets are 2x: tiles shrink instead of overshooting
    # the VMEM budget
    assert _pick_tile(128, itemsize=8) == 128
    assert _pick_tile(8192, itemsize=8) == 8
    assert _pick_tile(4096, itemsize=8) == 24


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**30), m=st.integers(2, 90),
       batch=st.integers(1, 40))
def test_kernel_property_sweep(seed, m, batch):
    lp = shuffle_batch(jax.random.key(seed + 1), normalize_batch(
        random_feasible_lp(jax.random.key(seed), batch, m)))
    r = solve_rgb(lp)
    k = solve_kernel(lp)
    np.testing.assert_allclose(np.asarray(r.objective),
                               np.asarray(k.objective),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# The loop that jumps each problem to its next violated constraint must
# answer exactly as the loop over every constraint did.
# ---------------------------------------------------------------------------

def _infeasible_midway(key, batch, m, dtype=jnp.float32):
    """Random feasible LPs in which every other problem gets x <= -60 and
    x >= 60 at two random positions: infeasible part-way through, with
    constraints still to come after it."""
    lp = random_feasible_lp(key, batch, m, dtype=dtype)
    A, b = np.array(lp.A), np.array(lp.b)
    pos = np.random.default_rng(m).permutation(m)[:2]
    A[::2, pos[0]], b[::2, pos[0]] = (1.0, 0.0), -60.0
    A[::2, pos[1]], b[::2, pos[1]] = (-1.0, 0.0), -60.0
    return make_batch(jnp.asarray(A), jnp.asarray(b), lp.c)


def _degenerate(key, batch, m, dtype=jnp.float32):
    """Every constraint line passes through one point a problem, so the
    re-solve meets zero offsets, ties and near-parallel lines; every
    third constraint repeats the one before it."""
    lp = random_feasible_lp(key, batch, m, dtype=dtype)
    A = np.array(lp.A)
    A[:, 2::3] = A[:, 1:-1:3][:, :A[:, 2::3].shape[1]]
    p = np.asarray(jax.random.uniform(jax.random.fold_in(key, 1),
                                      (batch, 1, 2), dtype, -20, 20))
    b = np.einsum("bmd,bmd->bm", A, np.broadcast_to(p, A.shape))
    return make_batch(jnp.asarray(A, dtype), jnp.asarray(b, dtype), lp.c)


def _mixed(key, batch, m, dtype=jnp.float32):
    """Feasible, ragged, infeasible and every-constraint-violated LPs in
    the same tiles."""
    k = jax.random.split(key, 3)
    q = batch // 4
    return concat_batches([
        random_feasible_lp(k[0], q, m, dtype=dtype),
        ragged_feasible_lp(k[1], q, m, m_min=1, dtype=dtype),
        _infeasible_midway(k[2], q, m, dtype=dtype),
        adversarial_lp(batch - 3 * q, m, dtype=dtype),
    ])


_MAKERS = {
    "paper": lambda key, B, m, dt: random_feasible_lp(key, B, m, dtype=dt),
    "ragged": lambda key, B, m, dt: ragged_feasible_lp(key, B, m, m_min=1,
                                                       dtype=dt),
    "infeasible": lambda key, B, m, dt: _infeasible_midway(key, B, m, dt),
    "infeasible_first": lambda key, B, m, dt: infeasible_lp(B, m, dtype=dt),
    "degenerate": _degenerate,
    "every_violated": lambda key, B, m, dt: adversarial_lp(B, m, dtype=dt),
    "mixed": _mixed,
}


def _packed(kind, batch, m, dtype=jnp.float32):
    lp = normalize_batch(_MAKERS[kind](jax.random.key(batch * 7 + m),
                                       batch, m, dtype))
    return ops.pack_constraints(lp)


def _bits(a):
    a = np.asarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


# (kind, batch, m, tile, chunk)
_BIT_CASES = [
    ("paper", 16, 1, 8, 0),
    ("paper", 64, 63, 32, 0),
    ("paper", 128, 64, 128, 0),
    ("paper", 40, 100, 8, 128),
    ("paper", 64, 128, 32, 128),
    ("paper", 128, 129, 128, 0),
    ("paper", 48, 129, 8, 128),
    ("paper", 128, 1024, 128, 0),
    ("paper", 64, 1024, 32, 128),
    ("ragged", 64, 200, 8, 0),
    ("ragged", 64, 300, 32, 128),
    ("infeasible", 32, 90, 8, 0),
    ("infeasible", 64, 260, 32, 128),
    ("infeasible_first", 16, 20, 8, 0),
    ("degenerate", 64, 100, 32, 0),
    ("degenerate", 32, 200, 8, 128),
    ("every_violated", 16, 150, 8, 0),
    ("every_violated", 32, 300, 32, 128),
    ("mixed", 128, 200, 128, 0),
    ("mixed", 64, 130, 32, 128),
]


@pytest.mark.parametrize("kind,batch,m,tile,chunk", _BIT_CASES,
                         ids=[f"{k}-B{b}-m{m}-t{t}-c{c}"
                              for k, b, m, t, c in _BIT_CASES])
def test_kernel_bit_identical_to_reference(kind, batch, m, tile, chunk):
    L, c, mv = _packed(kind, batch, m)
    x, feas, n = rgb_pallas(L, c, mv, M=DEFAULT_M, tile=tile, chunk=chunk,
                            interpret=True)
    x_ref, feas_ref = reference_rgb_pallas(L, c, mv, M=DEFAULT_M,
                                           tile=tile, chunk=chunk)
    assert np.array_equal(_bits(x), _bits(x_ref))
    assert np.array_equal(np.asarray(feas), np.asarray(feas_ref))
    if kind in ("infeasible", "infeasible_first", "mixed"):
        assert not np.asarray(feas).all()
    if kind == "every_violated":
        # every constraint is re-solved at first, so no step is skipped
        assert (np.asarray(n) >= min(m, 100)).all()


@pytest.mark.parametrize("kind,chunk", [("paper", 0), ("paper", 128),
                                        ("mixed", 0), ("degenerate", 128)])
def test_kernel_bit_identical_to_reference_float64(kind, chunk):
    with jax.enable_x64(True):
        L, c, mv = _packed(kind, 32, 140, jnp.float64)
        assert L.dtype == jnp.float64
        x, feas, _ = rgb_pallas(L, c, mv, M=DEFAULT_M, tile=8, chunk=chunk,
                                interpret=True)
        x_ref, feas_ref = reference_rgb_pallas(L, c, mv, M=DEFAULT_M,
                                               tile=8, chunk=chunk)
        assert np.array_equal(_bits(x), _bits(x_ref))
        assert np.array_equal(np.asarray(feas), np.asarray(feas_ref))


# ---------------------------------------------------------------------------
# The re-solve counter.
# ---------------------------------------------------------------------------

def _numpy_seidel(L, c, mv, M=DEFAULT_M):
    """Seidel's method over the packed rows in numpy, in the kernel's
    precision and with its box, tie-break and tolerances: each problem's
    re-solve count, x and feasibility."""
    L, c = np.asarray(L), np.asarray(c)
    dt = L.dtype.type
    ax, ay, bb = L[:, 0], L[:, 1], L[:, 2]
    mv = np.asarray(mv).reshape(-1)
    cx, cy = c[:, 0], c[:, 1]
    cpx, cpy = -cy, cx
    big, Mv = np.finfo(dt).max, dt(M)

    def sign_tb(v, tb):
        return np.where(np.abs(v) > oneD.EPS_TIE, np.sign(v),
                        np.where(np.abs(tb) > oneD.EPS_TIE, np.sign(tb),
                                 dt(1)))

    x = np.stack([sign_tb(cx, cpx) * Mv, sign_tb(cy, cpy) * Mv], 1)
    feas = np.ones(len(mv), bool)
    n = np.zeros(len(mv), np.int64)
    for i in range(int(mv.max())):
        lhs = ax[:, i] * x[:, 0] + ay[:, i] * x[:, 1]
        v = np.nonzero(feas & (i < mv)
                       & (lhs > bb[:, i] + dt(oneD.EPS_FEAS)))[0]
        if not v.size:
            continue
        n[v] += 1
        a_ix, a_iy, b_i = ax[v, i], ay[v, i], bb[v, i]
        p0x, p0y, ux, uy = a_ix * b_i, a_iy * b_i, -a_iy, a_ix
        axc, ayc, bbc = ax[v, :i], ay[v, :i], bb[v, :i]
        denom = axc * ux[:, None] + ayc * uy[:, None]
        num = bbc - (axc * p0x[:, None] + ayc * p0y[:, None])
        par = np.abs(denom) <= oneD.EPS_DENOM
        t = num / np.where(par, dt(1), denom)
        t_hi = np.min(np.where(denom > oneD.EPS_DENOM, t, big), axis=1,
                      initial=big)
        t_lo = np.max(np.where(denom < -oneD.EPS_DENOM, t, -big), axis=1,
                      initial=-big)
        bad = np.any(par & (num < -oneD.EPS_FEAS), axis=1)
        with np.errstate(divide="ignore", invalid="ignore"):
            for bd, bn in ((ux, Mv - p0x), (-ux, Mv + p0x),
                           (uy, Mv - p0y), (-uy, Mv + p0y)):
                t_hi = np.minimum(t_hi, np.where(bd > oneD.EPS_DENOM,
                                                 bn / bd, big))
                t_lo = np.maximum(t_lo, np.where(bd < -oneD.EPS_DENOM,
                                                 bn / bd, -big))
                bad |= (np.abs(bd) <= oneD.EPS_DENOM) & (bn < -oneD.EPS_FEAS)
        feas[v] = (t_lo <= t_hi + dt(oneD.EPS_FEAS)) & ~bad
        cu = cx[v] * ux + cy[v] * uy
        cpu = cpx[v] * ux + cpy[v] * uy
        tie = np.abs(cu) <= oneD.EPS_TIE
        tt = np.where((~tie & (cu > 0)) | (tie & (cpu > 0)), t_hi, t_lo)
        x[v, 0], x[v, 1] = p0x + tt * ux, p0y + tt * uy
    return n, x, feas


@pytest.mark.parametrize("kind,batch,m,tile,chunk", [
    ("paper", 64, 100, 32, 0),
    ("ragged", 64, 200, 8, 128),
    ("mixed", 128, 130, 128, 0),
    ("infeasible", 32, 90, 8, 128),
    ("every_violated", 16, 150, 8, 0),
])
def test_kernel_resolve_count_matches_numpy_seidel(kind, batch, m, tile,
                                                   chunk):
    L, c, mv = _packed(kind, batch, m)
    x, feas, n = rgb_pallas(L, c, mv, M=DEFAULT_M, tile=tile, chunk=chunk,
                            interpret=True)
    n_np, x_np, feas_np = _numpy_seidel(L, c, mv)
    np.testing.assert_array_equal(np.asarray(n)[:, 0], n_np)
    np.testing.assert_array_equal(np.asarray(feas)[:, 0].astype(bool),
                                  feas_np)
    np.testing.assert_allclose(np.asarray(x), x_np, rtol=1e-6, atol=1e-4)


def test_kernel_tile_loops_largest_count_plus_one(monkeypatch):
    # Count the kernel loop's condition checks per tile through a
    # callback wrapped around it: once per re-solve of the tile's busiest
    # problem, and once more to stop.
    checks = collections.Counter()
    while_loop = jax.lax.while_loop

    def counted(cond, body, init):
        tile = pl.program_id(0)

        def counted_cond(carry):
            jax.debug.callback(lambda t: checks.update([int(t)]), tile)
            return cond(carry)

        return while_loop(counted_cond, body, init)

    monkeypatch.setattr(jax.lax, "while_loop", counted)
    L, c, mv = _packed("mixed", 96, 150)
    _, _, n = rgb_pallas(L, c, mv, M=DEFAULT_M, tile=32, interpret=True)
    n = np.asarray(jax.block_until_ready(n))[:, 0]
    monkeypatch.undo()
    per_tile = n.reshape(-1, 32).max(axis=1) + 1
    assert [checks[t] for t in range(3)] == list(per_tile)
    # random orders take a few dozen; the last tile holds LPs in which
    # every constraint is violated, and takes them all
    assert (per_tile[:2] < 40).all() and per_tile[2] == 150 + 1


@pytest.mark.parametrize("m,lo,hi", [(64, 6.0, 9.0), (1024, 10.5, 15.0)])
def test_kernel_resolve_count_on_paper_generator(m, lo, hi):
    # About 2 ln m violations an LP under a random order: 7.1 at m 64
    # and 12.6 at m 1024 in a numpy simulation of this generator.
    lp = normalize_batch(random_feasible_lp(jax.random.key(m), 1024, m))
    sol, st = solve_kernel_with_stats(
        SolverSpec(backend="kernel", tile=128, normalize=False,
                   interpret=True), lp)
    n = np.asarray(st.resolves)
    assert n.shape == (1024,) and n.dtype == np.int32
    assert lo <= n.mean() <= hi
    assert (n.reshape(-1, 128).max(axis=1) + 1 < m).all()
    assert np.asarray(sol.feasible).all()


@pytest.mark.parametrize("tile", [8, None])
def test_kernel_stats_entry_matches_solve(tile):
    # The stats entry point answers exactly as Solver.solve, which
    # drops the count; padding rows (batch 20 on tile 8) are sliced off.
    lp = normalize_batch(ragged_feasible_lp(jax.random.key(11), 20, 70))
    spec = SolverSpec(backend="kernel", tile=tile, normalize=False,
                      interpret=True)
    sol, st = jax.jit(lambda b: solve_kernel_with_stats(spec, b))(lp)
    want = spec.build().solve(lp)
    assert isinstance(want, solver_mod.LPSolution)
    for field in ("x", "feasible", "objective"):
        assert np.array_equal(_bits(getattr(sol, field)),
                              _bits(getattr(want, field))), field
    assert st.resolves.shape == (20,)
    n_np, _, _ = _numpy_seidel(*ops.pack_constraints(lp))
    np.testing.assert_array_equal(np.asarray(st.resolves), n_np)


def test_kernel_stats_need_the_kernel_backend():
    lp = random_feasible_lp(jax.random.key(0), 8, 10)
    with pytest.raises(ValueError, match="kernel"):
        solve_kernel_with_stats(SolverSpec(backend="rgb"), lp)
