"""Pallas RGB kernel validation: shape/dtype sweeps in interpret mode
against the pure-jnp oracle (kernels.ref) and scipy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from _pad_reference import scatter_pad_packed, scatter_pad_packed_batch_dim

from repro.core import (infeasible_lp, normalize_batch, ragged_feasible_lp,
                        random_feasible_lp, shuffle_batch)
from repro.kernels import ops, ref
from repro.kernels.batch_lp import (VMEM_BUDGET_BYTES, _WORDS_PER_LANE,
                                    _pick_tile)
import repro.solver.solver as solver_mod
from repro.solver import SolverSpec, get_solver, solve_with_spec


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
