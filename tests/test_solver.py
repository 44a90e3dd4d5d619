"""The unified SolverSpec/Solver front end: validation, hashability,
jit/vmap composability, backend resolution, and cross-backend
equivalence properties (Seidel exact backends and the first-order pdhg
backend)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (LPBatch, adversarial_lp, infeasible_lp,
                        make_batch, pack, ragged_feasible_lp,
                        random_feasible_lp, split_batch, unpack)
from repro.solver import Solver, SolverSpec, get_solver, solve_with_spec

TOL_5SIG = 5e-4  # the paper's 5-significant-figure comparison tolerance


# -- spec validation & hashing -------------------------------------------

def test_spec_validates_at_construction():
    SolverSpec()  # defaults are valid
    SolverSpec(backend="pdhg", iter_block=64, restart_period=0,
               tol=1e-6, max_iters=5000)  # pdhg knobs on pdhg: fine
    with pytest.raises(ValueError) as err:
        SolverSpec(backend="bogus")
    for name in ("naive", "rgb", "kernel", "pdhg", "auto"):
        assert name in str(err.value)  # error lists the full backend set
    with pytest.raises(ValueError):
        SolverSpec(tile=0)
    with pytest.raises(ValueError):
        SolverSpec(chunk=-1)
    with pytest.raises(ValueError):
        SolverSpec(M=0.0)
    with pytest.raises(ValueError):
        SolverSpec(M=-5.0)
    with pytest.raises(ValueError):
        SolverSpec(dtype="int32")
    with pytest.raises(ValueError):
        SolverSpec(seed="zero")
    # pdhg-only knobs are rejected on every other backend, auto included
    with pytest.raises(ValueError, match="pdhg-only"):
        SolverSpec(backend="rgb", tol=1e-6)
    with pytest.raises(ValueError, match="pdhg-only"):
        SolverSpec(backend="auto", iter_block=64)
    with pytest.raises(ValueError, match="pdhg-only"):
        SolverSpec(backend="kernel", restart_period=512, max_iters=100)
    # and value-validated on pdhg itself
    with pytest.raises(ValueError):
        SolverSpec(backend="pdhg", iter_block=0)
    with pytest.raises(ValueError):
        SolverSpec(backend="pdhg", restart_period=-1)
    with pytest.raises(ValueError):
        SolverSpec(backend="pdhg", tol=0.0)
    with pytest.raises(ValueError):
        SolverSpec(backend="pdhg", max_iters=0)


def test_spec_hashable_value_semantics():
    a = SolverSpec(backend="rgb", tile=8, chunk=64)
    b = SolverSpec(backend="rgb", tile=8, chunk=64)
    assert a == b and hash(a) == hash(b)
    assert len({a, b}) == 1
    assert a != SolverSpec(backend="rgb", tile=16, chunk=64)
    # M normalises to float so 1e4 (int or float) hash equal
    assert SolverSpec(M=10000) == SolverSpec(M=10000.0)


def test_spec_resolution():
    platform = jax.default_backend()
    r = SolverSpec(backend="auto").resolve()
    assert r.is_resolved
    assert r.backend == ("kernel" if platform == "tpu" else "rgb")
    k = SolverSpec(backend="kernel").resolve("cpu")
    assert k.interpret is True
    assert SolverSpec(backend="kernel").resolve("tpu").interpret is False
    # interpret is kernel-only and canonicalises away elsewhere
    assert SolverSpec(backend="rgb", interpret=True).resolve().interpret \
        is False
    # resolving an already-resolved spec is the identity
    assert r.resolve() is r
    # inert fields canonicalise: seed is pinned when shuffle=False, so
    # identical execution plans share one cache entry
    assert SolverSpec(backend="rgb", seed=5).resolve() == \
        SolverSpec(backend="rgb").resolve()
    assert SolverSpec(backend="rgb", seed=5, shuffle=True).resolve() != \
        SolverSpec(backend="rgb", shuffle=True).resolve()
    # unset launch geometry survives resolve() — it means "pick per
    # shape" and is pinned by resolve_for_shape (table, then heuristic)
    assert SolverSpec(backend="rgb").resolve().tile is None
    assert SolverSpec(backend="rgb").resolve().chunk is None
    assert SolverSpec(backend="kernel").resolve("cpu").tile is None


def test_spec_resolve_for_shape_heuristics():
    """With no tuning-table entry, resolve_for_shape pins exactly the
    pre-tuning heuristics; explicit values pass through untouched."""
    from repro.tune import TuningTable, use_table
    with use_table(TuningTable()):   # force table misses
        r = SolverSpec(backend="rgb").resolve_for_shape(21, 9)
        assert r.is_shape_resolved
        assert (r.tile, r.chunk) == (32, 0)
        k = SolverSpec(backend="kernel").resolve_for_shape(200, 64,
                                                           "cpu")
        assert k.tile is not None and k.chunk == 0
        e = SolverSpec(backend="rgb", tile=8,
                       chunk=64).resolve_for_shape(21, 9)
        assert (e.tile, e.chunk) == (8, 64)
        # resolving a shape-resolved spec is the identity
        assert r.resolve_for_shape(21, 9) is r


def test_float64_requires_x64():
    """dtype='float64' must refuse to run (not silently truncate to
    float32) unless jax x64 is enabled."""
    spec = SolverSpec(backend="rgb", dtype="float64")  # constructible
    if jax.config.jax_enable_x64:
        pytest.skip("x64 enabled in this process")
    with pytest.raises(ValueError, match="x64"):
        spec.build()
    with pytest.raises(ValueError, match="x64"):
        solve_with_spec(spec, random_feasible_lp(jax.random.key(0), 2, 4))


def test_float64_kernel_refused_on_tpu():
    """Mosaic lowers 32-bit types only: a float64 kernel spec fails at
    resolution on TPU, not deep in lowering."""
    spec = SolverSpec(backend="kernel", dtype="float64")
    with jax.enable_x64(True):
        with pytest.raises(ValueError, match="float64 on TPU"):
            spec.resolve(platform="tpu")
        assert spec.resolve(platform="cpu").interpret is True


def test_spec_as_static_jit_argument():
    lp = random_feasible_lp(jax.random.key(0), 8, 12)
    calls = []

    def solve(spec, batch):
        calls.append(spec)
        return solve_with_spec(spec, batch)

    f = jax.jit(solve, static_argnums=0)
    s1 = f(SolverSpec(backend="rgb"), lp)
    s2 = f(SolverSpec(backend="rgb"), lp)  # equal spec: cache hit
    s3 = f(SolverSpec(backend="naive"), lp)
    assert len(calls) == 2  # one trace per distinct spec
    np.testing.assert_array_equal(np.asarray(s1.x), np.asarray(s2.x))
    np.testing.assert_allclose(np.asarray(s1.x), np.asarray(s3.x),
                               rtol=1e-5, atol=1e-5)


# -- Solver behaviour ----------------------------------------------------

def test_build_and_solve_paths_agree():
    lp = random_feasible_lp(jax.random.key(1), 16, 20)
    spec = SolverSpec(backend="rgb", tile=8)
    solver = spec.build()
    assert isinstance(solver, Solver)
    a = solver.solve(lp)
    b = solver(lp)                       # composable pure call
    c = jax.jit(solver)(lp)              # under an outer jit
    np.testing.assert_array_equal(np.asarray(a.x), np.asarray(b.x))
    np.testing.assert_allclose(np.asarray(a.x), np.asarray(c.x),
                               rtol=1e-6, atol=1e-6)


def test_solver_shape_cache():
    solver = SolverSpec(backend="rgb").build()
    solver.solve(random_feasible_lp(jax.random.key(0), 8, 12))
    solver.solve(random_feasible_lp(jax.random.key(1), 8, 12))
    assert solver.cache_info()["n_entries"] == 1
    solver.solve(random_feasible_lp(jax.random.key(2), 16, 12))
    assert solver.cache_info()["n_entries"] == 2
    solver.solve(random_feasible_lp(jax.random.key(3), 8, 12),
                 key=jax.random.key(0))  # keyed variant is its own entry
    assert solver.cache_info()["n_entries"] == 3


def test_get_solver_shares_instances():
    assert get_solver(SolverSpec(backend="rgb")) is \
        get_solver(SolverSpec(backend="rgb"))
    if jax.default_backend() != "tpu":
        # auto resolves to rgb off-TPU, landing on the same Solver
        assert get_solver(SolverSpec(backend="auto")) is \
            get_solver(SolverSpec(backend="rgb"))


def test_solve_one():
    lp = random_feasible_lp(jax.random.key(2), 4, 15)
    solver = SolverSpec(backend="rgb").build()
    batch_sol = solver.solve(lp)
    one = solver.solve_one(lp.A[2], lp.b[2], lp.c[2])
    assert one.x.shape == (2,)
    np.testing.assert_allclose(np.asarray(one.x),
                               np.asarray(batch_sol.x[2]),
                               rtol=1e-5, atol=1e-5)
    assert bool(one.feasible) == bool(batch_sol.feasible[2])


def test_shuffle_policy():
    lp = random_feasible_lp(jax.random.key(3), 8, 25)
    base = SolverSpec(backend="rgb")
    shuf = SolverSpec(backend="rgb", shuffle=True, seed=7)
    # spec-level shuffle == explicit per-call key with the same seed
    a = get_solver(shuf).solve(lp)
    b = get_solver(base).solve(lp, key=jax.random.key(7))
    np.testing.assert_array_equal(np.asarray(a.x), np.asarray(b.x))
    # a per-call key overrides the spec seed
    c = get_solver(shuf).solve(lp, key=jax.random.key(11))
    d = get_solver(base).solve(lp, key=jax.random.key(11))
    np.testing.assert_array_equal(np.asarray(c.x), np.asarray(d.x))
    # either way the optimum is order-invariant to tolerance
    np.testing.assert_allclose(np.asarray(a.objective),
                               np.asarray(c.objective),
                               rtol=TOL_5SIG, atol=TOL_5SIG)


def test_solver_vmap_composable():
    lp = random_feasible_lp(jax.random.key(4), 6, 10)
    stack = lambda a: jnp.stack([a, a])
    stacked = LPBatch(A=stack(lp.A), b=stack(lp.b), c=stack(lp.c),
                      m_valid=stack(lp.m_valid))
    for backend in ("naive", "rgb"):
        solver = get_solver(SolverSpec(backend=backend))
        flat = solver(lp)
        vs = jax.vmap(solver)(stacked)
        assert vs.x.shape == (2, 6, 2)
        np.testing.assert_allclose(np.asarray(vs.x[0]),
                                   np.asarray(flat.x),
                                   rtol=1e-5, atol=1e-5)


def test_dtype_cast_on_entry():
    lp = random_feasible_lp(jax.random.key(5), 4, 8)
    half = LPBatch(A=lp.A.astype(jnp.bfloat16),
                   b=lp.b.astype(jnp.bfloat16),
                   c=lp.c.astype(jnp.bfloat16), m_valid=lp.m_valid)
    sol = get_solver(SolverSpec(backend="rgb")).solve(half)
    assert sol.x.dtype == jnp.float32
    # A matching the spec dtype must not let mixed b/c leak through
    mixed = LPBatch(A=lp.A, b=lp.b.astype(jnp.bfloat16),
                    c=lp.c.astype(jnp.float16), m_valid=lp.m_valid)
    ref = get_solver(SolverSpec(backend="rgb")).solve(
        LPBatch(A=lp.A, b=lp.b.astype(jnp.bfloat16).astype(jnp.float32),
                c=lp.c.astype(jnp.float16).astype(jnp.float32),
                m_valid=lp.m_valid))
    got = get_solver(SolverSpec(backend="rgb")).solve(mixed)
    assert got.x.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got.x), np.asarray(ref.x))


# -- satellite regressions (core.lp) -------------------------------------

def test_make_batch_coerces_mismatched_dtypes():
    A = np.random.default_rng(0).normal(size=(3, 4, 2)).astype(np.float32)
    b = np.ones((3, 4), np.float64)      # mismatched: wider than A
    c = np.ones((3, 2), np.float16)      # mismatched: narrower than A
    batch = make_batch(A, b, c)
    assert batch.A.dtype == batch.b.dtype == batch.c.dtype == jnp.float32
    # integer A promotes to a float solve dtype
    bi = make_batch(np.ones((2, 3, 2), np.int32), np.ones((2, 3)),
                    np.ones((2, 2)))
    assert bi.A.dtype == jnp.float32
    # mixed inputs survive the full solve path
    sol = SolverSpec(backend="rgb").build().solve(batch)
    assert sol.x.dtype == jnp.float32


def test_split_batch_rejects_silent_remainder():
    lp = random_feasible_lp(jax.random.key(9), 8, 6)
    with pytest.raises(ValueError, match="allow_remainder"):
        split_batch(lp, [3, 2])          # 5 < 8: would drop 3 problems
    parts = split_batch(lp, [3, 2], allow_remainder=True)
    assert [p.batch for p in parts] == [3, 2]
    np.testing.assert_array_equal(np.asarray(parts[1].A),
                                  np.asarray(lp.A[3:5]))
    exact = split_batch(lp, [5, 3])      # exact cover still fine
    assert [p.batch for p in exact] == [5, 3]
    with pytest.raises(ValueError):
        split_batch(lp, [5, 4])          # overflow still rejected


# -- packed path: bit-identity against AoS --------------------------------

def _satellite_batch(kind: str):
    if kind == "adversarial":
        return adversarial_lp(6, 24)
    if kind == "infeasible":
        return infeasible_lp(5, 12)
    return ragged_feasible_lp(jax.random.key(13), 9, 21, m_min=2)


@pytest.mark.parametrize("backend", ["naive", "rgb", "kernel"])
@pytest.mark.parametrize("kind", ["adversarial", "infeasible", "ragged"])
def test_packed_path_bit_identical(kind, backend):
    """Solving a pre-packed batch must be *bit-identical* to solving the
    AoS batch it came from, on every backend — the layout is a
    representation change, not a numerical one."""
    lp = _satellite_batch(kind)
    spec = SolverSpec(backend=backend,
                      interpret=True if backend == "kernel" else None)
    solver = get_solver(spec)
    aos = solver.solve(lp)
    soa = solver.solve(pack(lp))
    np.testing.assert_array_equal(np.asarray(aos.x), np.asarray(soa.x))
    np.testing.assert_array_equal(np.asarray(aos.feasible),
                                  np.asarray(soa.feasible))
    np.testing.assert_array_equal(np.asarray(aos.objective),
                                  np.asarray(soa.objective))


def test_packed_path_bit_identical_with_shuffle():
    """The packed shuffle draws the same permutation as the AoS one
    (same key, same score shape), so bit-identity survives shuffling."""
    lp = _satellite_batch("ragged")
    spec = SolverSpec(backend="rgb", shuffle=True, seed=5)
    aos = get_solver(spec).solve(lp)
    soa = get_solver(spec).solve(pack(lp))
    np.testing.assert_array_equal(np.asarray(aos.x), np.asarray(soa.x))
    np.testing.assert_array_equal(np.asarray(aos.feasible),
                                  np.asarray(soa.feasible))


def test_padded_pack_shuffle_agrees_to_tolerance():
    """Documented caveat: padding the constraint axis changes the shape
    the shuffle scores are drawn from, so a bucket-padded pack is *not*
    bit-identical under shuffle=True — but the optimum is
    order-invariant, so objectives still agree to the paper's
    tolerance (and without shuffle, padding preserves bit-identity)."""
    lp = _satellite_batch("ragged")
    shuf = SolverSpec(backend="rgb", shuffle=True, seed=5)
    a = get_solver(shuf).solve(lp)
    p = get_solver(shuf).solve(pack(lp, m_pad=128))
    np.testing.assert_array_equal(np.asarray(a.feasible),
                                  np.asarray(p.feasible))
    np.testing.assert_allclose(np.asarray(a.objective),
                               np.asarray(p.objective),
                               rtol=TOL_5SIG, atol=TOL_5SIG)
    plain = SolverSpec(backend="rgb")
    np.testing.assert_array_equal(
        np.asarray(get_solver(plain).solve(lp).x),
        np.asarray(get_solver(plain).solve(pack(lp, m_pad=128)).x))


# -- cross-backend equivalence property -----------------------------------

_GENERATORS = ("random", "ragged", "infeasible")


def _gen_batch(kind: str, seed: int, batch: int, m: int):
    if kind == "random":
        return random_feasible_lp(jax.random.key(seed), batch, m)
    if kind == "ragged":
        return ragged_feasible_lp(jax.random.key(seed), batch, max(m, 5),
                                  m_min=2)
    return infeasible_lp(batch, m)


@settings(max_examples=10, deadline=None)
@given(kind=st.sampled_from(_GENERATORS), seed=st.integers(0, 2**30),
       batch=st.integers(1, 12), m=st.integers(3, 40))
def test_backends_agree_property(kind, seed, batch, m):
    """naive, rgb (dense and chunked) and kernel(interpret) agree on
    feasibility and on the objective to the paper's 5-significant-figure
    tolerance, across random/ragged/infeasible generators."""
    lp = _gen_batch(kind, seed, batch, m)
    # pack/unpack round-trip law: the packed layout is lossless
    rt = unpack(pack(lp))
    for f in ("A", "b", "c", "m_valid"):
        np.testing.assert_array_equal(np.asarray(getattr(rt, f)),
                                      np.asarray(getattr(lp, f)))
    sweep = (
        SolverSpec(backend="naive", shuffle=True, seed=seed),
        SolverSpec(backend="rgb", shuffle=True, seed=seed),
        SolverSpec(backend="rgb", tile=8, chunk=64, shuffle=True,
                   seed=seed),
        SolverSpec(backend="kernel", interpret=True, shuffle=True,
                   seed=seed),
    )
    sols = [get_solver(s).solve(lp) for s in sweep]
    ref = sols[0]
    for spec, sol in zip(sweep[1:], sols[1:]):
        np.testing.assert_array_equal(
            np.asarray(ref.feasible), np.asarray(sol.feasible),
            err_msg=f"feasibility mismatch: {spec}")
        feas = np.asarray(ref.feasible)
        if feas.any():
            np.testing.assert_allclose(
                np.asarray(ref.objective)[feas],
                np.asarray(sol.objective)[feas],
                rtol=TOL_5SIG, atol=TOL_5SIG,
                err_msg=f"objective mismatch: {spec}")
    # the first-order backend classifies feasibility identically and
    # matches the exact optimum to its KKT stopping tolerance (looser
    # than the vertex-exact Seidel agreement above)
    pdhg = get_solver(SolverSpec(backend="pdhg", tol=1e-5)).solve(lp)
    np.testing.assert_array_equal(np.asarray(ref.feasible),
                                  np.asarray(pdhg.feasible),
                                  err_msg="feasibility mismatch: pdhg")
    feas = np.asarray(ref.feasible)
    if feas.any():
        np.testing.assert_allclose(np.asarray(ref.objective)[feas],
                                   np.asarray(pdhg.objective)[feas],
                                   rtol=2e-3, atol=2e-3,
                                   err_msg="objective mismatch: pdhg")
