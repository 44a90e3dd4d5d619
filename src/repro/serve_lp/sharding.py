"""Executable builder: one ExecSpec -> one dispatch/complete Executable.

A :class:`~repro.serve_lp.mesh_layout.MeshLayout` plans how the
flushed super-batch spreads over devices: per-device row counts (uneven
shards allowed; unused devices get zero rows), and each
:class:`~repro.serve_lp.mesh_layout.LaunchGroup` compiles to
``jax.jit(shard_map(solve))`` over a contiguous sub-mesh.  The planner
owns padding: ``b_pad`` only needs to be positive — rows are padded
with neutral LPs up to whole kernel tiles here, never up to
``tile * n_devices`` blocks, so a prime-sized flush on 4 devices is
legal.  A group of one device compiles to plain ``jax.jit``.  Tests
assert the result is bit-identical to one plain-jit launch.

Built executables are *two-stage* so the serve loop can pipeline:

* :meth:`Executable.dispatch` takes the scheduler's packed host buffers
  ``(L (B, 4, m), c (B, 2), mv (B, 1))`` already padded to the spec's
  shapes and returns an opaque handle (device arrays).  JAX dispatch is
  asynchronous, so the call returns while the solve is still in flight
  — nothing on this path materializes host numpy.
* :meth:`Executable.complete` blocks until the device is done and
  materializes host numpy ``(x (B, 2), feasible (B,) bool)`` — the
  scheduler's completion worker scatters those rows straight into
  per-request futures.

Calling the executable like a function composes the two stages
synchronously (the pre-pipelining contract; tests and one-off callers
use it).  On backends where XLA honours buffer donation (GPU/TPU) the
packed ``L`` block is donated, killing the device-side defensive copy
of the largest flush input; CPU ignores donation with a warning, so it
is gated off there.

The solve wraps the packed block in a
:class:`~repro.core.packed.PackedLPBatch` view (no repack) and runs the
same :func:`repro.solver.solve_with_spec` core as every other entry
point.  Because every problem row is independent, per-problem results
do not depend on which device solved them — sharding is pure layout.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.packed import PackedLPBatch
from repro.core.lp import PAD_B
from repro.serve_lp.buckets import ExecSpec
from repro.serve_lp.mesh_layout import (
    DATA_AXIS,
    MeshLayout,
    make_mesh,
    plan_layout,
)
from repro.solver import solve_with_spec

# Platforms where XLA actually honours input buffer donation; CPU
# ignores it (with a "donated buffers were not usable" warning), so
# donation is gated to keep test/CI logs clean.
_DONATING_PLATFORMS = ("gpu", "tpu", "cuda", "rocm")

# Opt-in per-launch jax.profiler.TraceAnnotation around each mesh
# launch-group dispatch, so device-profiler timelines carry the same
# launch labels as the host-side device.solve spans.  Off by default:
# the annotation context costs a little per launch and is only useful
# under an active profiler session.
_ANNOTATE_LAUNCHES = False


def set_launch_annotations(enabled: bool) -> None:
    """Enable/disable per-launch-group profiler annotations (the
    scheduler flips this on when its tracer was built with
    ``annotate_device=True``)."""
    global _ANNOTATE_LAUNCHES
    _ANNOTATE_LAUNCHES = bool(enabled)


def _make_solve(spec: ExecSpec) -> Callable:
    """The per-shard solve as a pure jax function of the packed arrays —
    the same :func:`repro.solver.solve_with_spec` core every other
    entry point runs through, so scheduler round-trips stay
    bit-identical to direct solves with the same spec."""

    def solve(L, c, mv):
        sol = solve_with_spec(spec.solver,
                              PackedLPBatch(L=L, c=c, m_valid=mv))
        return sol.x, sol.feasible

    return solve


class Executable:
    """A compiled flush solver split into dispatch and complete stages.

    ``dispatch(L, c, mv)`` enqueues the solve and returns an opaque
    handle without synchronizing; ``complete(handle)`` blocks until the
    device is done and returns host numpy ``(x, feasible)``.  The
    object is also callable — ``exe(L, c, mv)`` is the synchronous
    composition of the two stages.

    ``donated`` records whether the packed ``L`` input is donated to
    XLA (its device buffer is reused for outputs; the *host* buffer is
    unaffected and still owned by the flush-buffer pool until the
    flush completes).  ``layout`` is the :class:`MeshLayout` the
    executable was planned with (``None`` for injected executables); ``shards``/``n_launches`` expose the per-device row
    counts and device-launch count for metrics.
    """

    __slots__ = ("_dispatch", "_complete", "donated", "layout")

    def __init__(self, dispatch: Callable, complete: Callable, *,
                 donated: bool = False,
                 layout: Optional[MeshLayout] = None):
        self._dispatch = dispatch
        self._complete = complete
        self.donated = donated
        self.layout = layout

    @property
    def shards(self) -> Tuple[int, ...]:
        return self.layout.shards if self.layout is not None else ()

    @property
    def n_launches(self) -> int:
        return self.layout.n_launches if self.layout is not None else 1

    def dispatch(self, L, c, mv) -> Any:
        """Enqueue the solve; returns the in-flight result handle."""
        return self._dispatch(L, c, mv)

    def complete(self, handle) -> Tuple[np.ndarray, np.ndarray]:
        """Block until ``handle``'s solve finishes; host ``(x, feas)``."""
        return self._complete(handle)

    def __call__(self, L, c, mv) -> Tuple[np.ndarray, np.ndarray]:
        return self.complete(self.dispatch(L, c, mv))


def as_executable(fn) -> Executable:
    """Adapt a plain synchronous callable to the dispatch/complete
    protocol: its whole solve runs at dispatch time and ``complete`` is
    the identity.  Objects already exposing ``dispatch``/``complete``
    (built :class:`Executable`\\ s, test doubles) pass through unchanged,
    so injected caches keep working in the pipelined serve loop."""
    if hasattr(fn, "dispatch") and hasattr(fn, "complete"):
        return fn
    return Executable(fn, lambda handle: handle)


def _pad_rows(L, c, mv, b_pad: int):
    """Extend host buffers with neutral LPs (always-feasible, m_valid=0)
    up to ``b_pad`` rows — the planner-owned padding for flush sizes
    that are not whole-tile multiples."""
    n = b_pad - L.shape[0]
    if n <= 0:
        return L, c, mv
    Lp = np.zeros((n,) + L.shape[1:], dtype=L.dtype)
    Lp[:, 2, :] = PAD_B
    cp = np.zeros((n, 2), dtype=c.dtype)
    cp[:, 0] = 1.0
    mvp = np.zeros((n, 1), dtype=mv.dtype)
    return (np.concatenate([L, Lp]), np.concatenate([c, cp]),
            np.concatenate([mv, mvp]))


def group_launch(solve, group_devs, donate_kw=None):
    """One launch group's jitted solve and the input placement for it:
    plain ``jax.jit`` on a single device, ``jax.jit(shard_map)`` over a
    1-D mesh of several.  Inputs are put on their devices with the
    returned sharding before the call: left to jit, uncommitted host
    numpy lands on the default device first."""
    donate_kw = donate_kw or {}
    if len(group_devs) == 1:
        return (jax.jit(solve, **donate_kw),
                SingleDeviceSharding(group_devs[0]))
    mesh = make_mesh(group_devs)
    # check_vma=False: every in/out is sharded over DATA_AXIS (nothing
    # replicated to check) and the pallas_call kernel backend has no
    # replication rule at all.
    fn = jax.jit(
        jax.shard_map(
            solve, mesh=mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(P(DATA_AXIS), P(DATA_AXIS)),
            check_vma=False),
        **donate_kw)
    return fn, NamedSharding(mesh, P(DATA_AXIS))


def _build_mesh_executable(spec: ExecSpec, devices, solve,
                           donate_kw) -> Executable:
    """Plan a :class:`MeshLayout` for the spec and compile one
    launch per :class:`LaunchGroup` (uneven layouts need at most two).
    Each group jits over its own contiguous devices, so group launches
    land on disjoint devices and overlap."""
    layout = plan_layout(spec.b_pad, spec.solver.tile, len(devices))
    launches = [
        (g.offset, g.rows) + group_launch(
            solve, devices[g.start:g.start + g.n_devices], donate_kw)
        for g in layout.groups]

    b_pad = spec.b_pad
    labels = tuple(
        f"launch d{g.start}+{g.n_devices} rows{g.rows} m{spec.bucket_m}"
        for g in layout.groups)

    def dispatch(L, c, mv):
        if L.shape[0] != layout.b_pad:
            L, c, mv = _pad_rows(L, c, mv, layout.b_pad)
        out = []
        for (o, n, fn, placement), label in zip(launches, labels):
            args = jax.device_put((L[o:o + n], c[o:o + n], mv[o:o + n]),
                                  placement)
            if _ANNOTATE_LAUNCHES:
                with jax.profiler.TraceAnnotation(label):
                    out.append(fn(*args))
            else:
                out.append(fn(*args))
        return tuple(out)

    def complete(handles):
        xs = [np.asarray(h[0]) for h in handles]
        fs = [np.asarray(h[1]) for h in handles]
        x = xs[0] if len(xs) == 1 else np.concatenate(xs)
        feas = fs[0] if len(fs) == 1 else np.concatenate(fs)
        return x[:b_pad], feas[:b_pad]

    return Executable(dispatch, complete,
                      donated=bool(donate_kw), layout=layout)


def build_executable(
    spec: ExecSpec,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Executable:
    """Compile-on-first-call solver for one spec.  ``devices`` defaults
    to ``jax.devices()``."""
    devices = list(devices) if devices is not None else jax.devices()
    if len(devices) != spec.n_devices:
        raise ValueError(
            f"spec.n_devices={spec.n_devices} != len(devices)="
            f"{len(devices)}")
    solve = _make_solve(spec)
    donate = all(d.platform in _DONATING_PLATFORMS for d in devices)
    donate_kw = {"donate_argnums": (0,)} if donate else {}
    return _build_mesh_executable(spec, devices, solve, donate_kw)
