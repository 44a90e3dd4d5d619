"""Shape bucketing and the executable cache.

Heterogeneous request streams would otherwise produce one XLA compile per
distinct ``(B, m)`` — the bucketing here rounds both dimensions up a small
geometric ladder so steady-state traffic lands on a bounded set of
executables:

* the constraint dimension ``m`` rounds up to ``base * 2^k`` — base is
  LANE (128) for the Pallas kernel, which needs a 128-lane multiple
  anyway, and 8 for the dense solvers, which have no layout requirement
  and should not pad an m=8 LP 16x (doubling bounds waste at 2x and
  caps the ladder at ~log2(m_max/base) rungs);
* the batch dimension rounds up to ``tile * 2^k`` — one kernel
  ``tile`` is the unit, since the MeshLayout planner owns any further
  per-device padding; doubling again bounds the rung count.

The :class:`ExecutableCache` maps an :class:`ExecSpec` (the full shape +
method key) to a built solver executable and counts hits/misses so the
serving metrics can prove the bucketing works.  Since the serve loop
went pipelined, built entries are two-stage
:class:`~repro.serve_lp.sharding.Executable` objects (async ``dispatch``
returning device handles + blocking ``complete`` materializing host
numpy); plain synchronous callables are still accepted — the scheduler
adapts them via :func:`~repro.serve_lp.sharding.as_executable` — so
injected test builders keep working.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Dict, List

from repro.kernels.batch_lp import LANE
from repro.solver import SolverSpec
# One ladder implementation serves serving buckets *and* tuning-table
# shape classes — their alignment is what makes table lookups for a
# flush's bucket land on the entries the tuner recorded.
from repro.tune.table import bucket_pow2


def bucket_m(m: int, *, base: int = LANE) -> int:
    """Round a constraint count up to the geometric LANE ladder
    {base, 2*base, 4*base, ...}."""
    if m < 1:
        raise ValueError(f"m={m} < 1")
    return bucket_pow2(m, base)


def bucket_batch(batch: int, unit: int) -> int:
    """Round a flush size up to the geometric ladder of ``unit``
    multiples {unit, 2*unit, 4*unit, ...}."""
    if batch < 1:
        raise ValueError(f"batch={batch} < 1")
    return bucket_pow2(batch, unit)


def shape_ladder(m_max: int, *, base: int = LANE) -> List[int]:
    """All m-buckets needed to cover constraint counts up to ``m_max``."""
    out = [base]
    while out[-1] < m_max:
        out.append(out[-1] * 2)
    return out


@dataclasses.dataclass(frozen=True)
class ExecSpec:
    """Everything that determines a compiled solver executable: the
    padded shapes, the device count and the full (resolved)
    :class:`~repro.solver.SolverSpec`.

    Embedding the whole solver spec in the cache key is deliberate —
    two schedulers with different specs (dtype, shuffle seed, M, ...)
    can never alias each other's executables."""

    bucket_m: int      # padded constraint count (LANE multiple)
    b_pad: int         # padded batch size (any positive count)
    solver: SolverSpec
    n_devices: int = 1

    def __post_init__(self):
        if not isinstance(self.solver, SolverSpec):
            raise TypeError(
                f"solver must be a SolverSpec, got {type(self.solver)!r}")
        # Canonicalise so equal execution plans hash equal.
        object.__setattr__(self, "solver", self.solver.resolve())
        if self.solver.tile is None:
            raise ValueError(
                "ExecSpec needs a concrete solver.tile (shards are "
                "whole numbers of tiles)")
        if self.bucket_m < 1:
            raise ValueError(f"bucket_m={self.bucket_m} < 1")
        if self.b_pad < 1:
            raise ValueError(f"b_pad={self.b_pad} < 1")
        # Only the Pallas kernel has a lane-layout requirement.
        if self.solver.backend == "kernel" and self.bucket_m % LANE:
            raise ValueError(f"bucket_m={self.bucket_m} not a {LANE} "
                             "multiple")


class ExecutableCache:
    """spec -> built executable, with hit/miss accounting.

    ``builder`` is called under the cache lock on a miss; the returned
    executable (a dispatch/complete
    :class:`~repro.serve_lp.sharding.Executable` or any callable) is
    stored and reused for every later flush with the same spec.  (The
    first *invocation* still pays the XLA compile — the cache bounds
    how often that happens, it does not hide it.)  One cached
    executable may serve several concurrently in-flight flushes of the
    same spec: dispatch/complete hold no per-flush state, so that is
    safe by construction.
    """

    def __init__(self, builder: Callable[[ExecSpec], Callable]):
        self._builder = builder
        self._cache: Dict[ExecSpec, Callable] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get(self, spec: ExecSpec) -> Callable:
        with self._lock:
            fn = self._cache.get(spec)
            if fn is not None:
                self.hits += 1
                return fn
            self.misses += 1
            fn = self._cache[spec] = self._builder(spec)
            return fn

    def __len__(self) -> int:
        return len(self._cache)

    def stats(self) -> Dict[str, float]:
        total = self.hits + self.misses
        return {
            "hits": self.hits,
            "misses": self.misses,
            "size": len(self._cache),
            "hit_rate": self.hits / total if total else 0.0,
        }

    def reset_stats(self) -> None:
        """Zero the hit/miss counters but keep built executables — used
        after a warmup pass so reports show steady-state behaviour."""
        with self._lock:
            self.hits = 0
            self.misses = 0

    def clear(self) -> None:
        with self._lock:
            self._cache.clear()
            self.hits = 0
            self.misses = 0
