"""Unified front end for the batch 2-D LP solver stack.

One operation, many LPs, every backend::

    from repro.solver import SolverSpec

    solver = SolverSpec(backend="auto", shuffle=True).build()
    sol = solver.solve(batch)            # jit-cached per input shape
    one = solver.solve_one(A, b, c)      # single-LP convenience
    sol = jax.jit(solver)(batch)         # composable pure call
    sol = solver.solve(batch.pack())     # packed SoA batches solve
                                         # bit-identically, no repack

    # same problem, every backend, bit-for-bit comparable:
    sweep = [SolverSpec(backend=b, interpret=True if b == "kernel"
                        else None)
             for b in ("naive", "rgb", "kernel", "pdhg")]
    sols = [s.build().solve(batch) for s in sweep]

    # the kernel's work per LP (Seidel re-solves), on request only:
    sol, stats = solve_kernel_with_stats(SolverSpec(backend="kernel"),
                                         batch)

:class:`SolverSpec` is frozen and hashable — use it directly as a
static ``jax.jit`` argument or as an executable-cache key (the serving
layer's ``ExecSpec`` embeds one).

The exact Seidel backends (``naive``/``rgb``/``kernel``) answer to
machine precision at 2-D/small-m; ``backend="pdhg"`` is the restarted
first-order backend (:mod:`repro.pdhg`) that scales m into the
thousands and answers to a KKT tolerance.  ``backend="auto"`` routes
each input shape to the fastest *measured* backend when the tuning
table has entries.

Launch geometry left unset (``tile``/``chunk`` ``None``) is pinned per
input shape with the precedence *explicit > measured tuning table >
heuristic* (see :mod:`repro.tune` and
:meth:`SolverSpec.resolve_for_shape`).
"""
from repro.solver.solver import (KernelStats, Solver,
                                 solve_kernel_with_stats, solve_with_spec)
from repro.solver.spec import (BACKENDS, DEFAULT_M, SolverSpec,
                               get_solver)

__all__ = [
    "BACKENDS", "DEFAULT_M", "KernelStats", "Solver", "SolverSpec",
    "get_solver", "solve_kernel_with_stats", "solve_with_spec",
]
