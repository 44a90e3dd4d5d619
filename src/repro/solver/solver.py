"""`Solver` — a reusable, jit-cached executor for one :class:`SolverSpec`.

``spec.build()`` resolves ``"auto"`` choices against the current JAX
backend and returns a Solver that

* caches one jit-compiled callable per input shape (``solve``), so a
  stream of same-shaped batches compiles exactly once;
* stays composable: ``solver(batch)`` is a pure traceable function of
  the batch, safe under an outer ``jax.jit``/``jax.vmap``;
* offers ``solve_one(A, b, c)`` for the single-LP convenience case.

``solve_with_spec`` is the underlying pure function (spec in Python,
arrays traced).  Every layer — the benchmarks, the tuner, the serving
executables in ``serve_lp.sharding`` — runs through it, which is what
makes "same problem, every backend, bit-for-bit comparable" a
one-liner.

Both entry points accept either constraint layout: the AoS
:class:`~repro.core.lp.LPBatch` or the packed SoA
:class:`~repro.core.packed.PackedLPBatch`.  A packed batch stays packed
end-to-end — normalise/shuffle run in their packed-native forms, the
kernel backend consumes ``L`` directly, and the dense backends consume
the ``L`` component rows directly too (``seidel.solve_*_packed``; no
AoS round-trip anywhere in the trace).  The AoS entry slices its
normals into the same rows, so both layouts run the identical graph
and ``solve(pack(batch))`` is bit-identical to ``solve(batch)``.  (One
caveat: padding the constraint axis — in *either* layout — changes the
score shape ``shuffle`` draws from, so for ``shuffle=True`` specs the
identity needs matching ``m``; a padded batch still agrees on the
optimum to the usual tolerance, just not bit-for-bit.)

Launch geometry left unset on the spec (``tile``/``chunk`` ``None``)
is pinned here per input shape via
:meth:`~repro.solver.spec.SolverSpec.resolve_for_shape` — explicit
values win, then the measured :mod:`repro.tune` table for this device,
then the static heuristics.
"""
from __future__ import annotations

from typing import Union

import jax
import jax.numpy as jnp

from repro.core.lp import (LPBatch, LPSolution, normalize_batch,
                           shuffle_batch)
from repro.core.packed import (PackedLPBatch, normalize_packed, pack,
                               pad_packed, pad_packed_batch_dim,
                               shuffle_packed)
from repro.core.seidel import (solve_naive, solve_naive_packed, solve_rgb,
                               solve_rgb_packed)
from repro.pdhg import solve_pdhg, solve_pdhg_packed
from repro.solver.spec import RGB_DEFAULT_TILE, SolverSpec

AnyLPBatch = Union[LPBatch, PackedLPBatch]

# The named scopes every device op of a solve falls under, one each, so
# that a profile splits a call into its stages: cast/normalise/shuffle/
# pack, padding to the kernel's lane and tile multiples, the backend, and
# slicing the padding off with the objective.
PREPARE, PAD, KERNEL, FINISH = ("solver.prepare", "solver.pad",
                                "solver.kernel", "solver.finish")
STAGES = (PREPARE, PAD, KERNEL, FINISH)


def solve_with_spec(spec: SolverSpec, batch: AnyLPBatch,
                    key=None) -> LPSolution:
    """Solve ``batch`` (AoS or packed) per ``spec`` — the pure,
    trace-safe core.

    ``key`` overrides the spec's shuffle policy for this call; with
    ``key=None`` the batch is shuffled iff ``spec.shuffle`` (keyed by
    ``spec.seed``).
    """
    is_packed = isinstance(batch, PackedLPBatch)
    m = batch.m_pad if is_packed else batch.m
    spec = spec.resolve_for_shape(m, batch.batch)
    with jax.named_scope(PREPARE):
        if key is None and spec.shuffle:
            key = jax.random.key(spec.seed)
        batch = _prepare(spec, batch, key)
        if spec.backend == "kernel" and not is_packed:
            batch = pack(batch)
    if spec.backend == "kernel":
        return _solve_kernel(spec, batch)
    with jax.named_scope(KERNEL):
        return _solve_dense(spec, batch)


def _prepare(spec: SolverSpec, batch: AnyLPBatch, key) -> AnyLPBatch:
    """Cast -> normalise -> shuffle, each in the batch's own layout (a
    packed batch never leaves SoA)."""
    dt = jnp.dtype(spec.dtype)
    # Cast each array (astype is the identity when already dt): A alone
    # matching must not let a mixed-dtype b or c leak through.
    if isinstance(batch, PackedLPBatch):
        batch = PackedLPBatch(L=batch.L.astype(dt), c=batch.c.astype(dt),
                              m_valid=batch.m_valid)
        if spec.normalize:
            batch = normalize_packed(batch)
        return batch if key is None else shuffle_packed(key, batch)
    batch = LPBatch(A=batch.A.astype(dt), b=batch.b.astype(dt),
                    c=batch.c.astype(dt), m_valid=batch.m_valid)
    if spec.normalize:
        batch = normalize_batch(batch)
    return batch if key is None else shuffle_batch(key, batch)


def _solve_dense(spec: SolverSpec, batch: AnyLPBatch) -> LPSolution:
    """The jnp backends; packed batches hand their ``L`` rows straight
    to the packed twin (no unpack in the trace)."""
    packed = isinstance(batch, PackedLPBatch)
    if spec.backend == "pdhg":
        return (solve_pdhg_packed if packed else solve_pdhg)(
            batch, M=spec.M, tol=spec.tol, max_iters=spec.max_iters,
            iter_block=spec.iter_block, restart_period=spec.restart_period)
    if spec.backend == "naive":
        return (solve_naive_packed if packed else solve_naive)(batch,
                                                               M=spec.M)
    return (solve_rgb_packed if packed else solve_rgb)(
        batch, M=spec.M, tile=spec.tile or RGB_DEFAULT_TILE,
        chunk=spec.chunk or 0)


def _solve_kernel(spec: SolverSpec, pb: PackedLPBatch) -> LPSolution:
    # Deferred import: kernels.ops wraps this module for its public
    # compatibility surface, so the dependency must point one way only.
    from repro.kernels.batch_lp import LANE, _pick_tile, rgb_pallas

    B = pb.batch
    with jax.named_scope(PAD):
        pb = pad_packed(pb, -(-pb.m_pad // LANE) * LANE)
        tile = spec.tile or _pick_tile(pb.m_pad, B,
                                       itemsize=pb.L.dtype.itemsize)
        run = pad_packed_batch_dim(pb, -(-B // tile) * tile)
    with jax.named_scope(KERNEL):
        x, feas = rgb_pallas(run.L, run.c, run.m_valid, M=spec.M,
                             tile=tile, chunk=spec.chunk,
                             interpret=spec.interpret)
    with jax.named_scope(FINISH):
        x, feas = x[:B], feas[:B, 0]
        return LPSolution(
            x=x,
            feasible=feas.astype(bool),
            objective=jnp.einsum("bd,bd->b", pb.c.astype(x.dtype), x),
        )


class Solver:
    """Executor for one resolved :class:`SolverSpec`.

    Construct via ``spec.build()`` (or :func:`~repro.solver.spec.
    get_solver` for the process-wide cached instance).
    """

    def __init__(self, spec: SolverSpec):
        if not isinstance(spec, SolverSpec):
            raise TypeError(f"expected SolverSpec, got {type(spec)!r}")
        self.spec = spec.resolve()
        # ``backend="auto"`` stays "auto" on the *solving* spec so each
        # input shape can pick the fastest measured backend from the
        # tuning table at trace time (``self.spec`` above is the
        # introspection view and the choice on a table miss).  Note the
        # process-wide :func:`~repro.solver.spec.get_solver` cache keys
        # on the resolved spec, so it pins "auto" to the platform
        # default; build a Solver via ``spec.build()`` to keep the
        # shape-dependent behaviour.
        solve_spec = spec if spec.backend == "auto" else self.spec
        self._solve_spec = solve_spec

        # jax.jit itself caches one compile per input shape/dtype; one
        # persistent wrapper per calling convention is all we need.  The
        # functions' names name the compiled modules (``jit_solve``,
        # ``jit_solve_keyed``) and their dispatches in a profile.
        def solve(batch):
            return solve_with_spec(solve_spec, batch)

        def solve_keyed(batch, key):
            return solve_with_spec(solve_spec, batch, key)

        self._jit_plain = jax.jit(solve)
        self._jit_keyed = jax.jit(solve_keyed)

    # -- composable entry point ------------------------------------------

    def __call__(self, batch: AnyLPBatch, key=None) -> LPSolution:
        """Pure function of ``(batch, key)`` — compose freely under an
        outer ``jax.jit`` / ``jax.vmap`` / ``jax.grad`` transform."""
        return solve_with_spec(self._solve_spec, batch, key)

    # -- jit-cached host entry points ------------------------------------

    def solve(self, batch: AnyLPBatch, key=None) -> LPSolution:
        """Solve one batch (AoS or packed) through the per-shape
        compile cache.  A ``solver.solve`` profiler span covers the
        host from entry until the asynchronous dispatch returns."""
        m = batch.m_pad if isinstance(batch, PackedLPBatch) else batch.m
        with jax.profiler.TraceAnnotation("solver.solve", batch=batch.batch,
                                          m=m):
            if key is None:
                return self._jit_plain(batch)
            return self._jit_keyed(batch, key)

    def solve_one(self, A, b, c, key=None) -> LPSolution:
        """Solve a single LP (``A (m,2)``, ``b (m,)``, ``c (2,)``);
        returns an :class:`LPSolution` with the batch axis dropped."""
        from repro.core.lp import make_batch
        sol = self.solve(make_batch(A, b, c), key=key)
        return LPSolution(x=sol.x[0], feasible=sol.feasible[0],
                          objective=sol.objective[0])

    def __repr__(self) -> str:
        return f"Solver({self.spec!r})"
