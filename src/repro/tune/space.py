"""Candidate launch-geometry enumeration.

A :class:`Candidate` is one ``(backend, tile, chunk)`` configuration
the tuner may time.  :func:`candidate_space` enumerates exactly the
configurations that are *valid* for a given ``(m_pad, batch, dtype,
device kind)`` — the constraints mirror the execution layers:

* ``naive`` has no launch geometry (vmap over problems): a single
  candidate, recorded with the serving-default tile so the entry can
  still drive the scheduler's batch ladder.
* ``rgb`` tiles are powers of two (8..256), clamped so a tile never
  exceeds the (sublane-rounded) batch; chunks are 0 (dense re-solve)
  or lane-sized blocks strictly smaller than the padded constraint
  count (a chunk >= m_pad degenerates to the dense variant).
* ``kernel`` tiles are sublane multiples capped at the Pallas
  ``DEFAULT_TILE`` and filtered by the same VMEM working-set budget
  ``_pick_tile`` uses (a candidate that cannot fit VMEM is not worth
  timing); chunks must divide the LANE-rounded ``m_pad`` exactly
  (``rgb_pallas`` rejects anything else).
* ``pdhg`` has no launch geometry — its knobs are the iteration
  schedule.  A pdhg candidate reinterprets the ``(tile, chunk)`` slots
  as ``(iter_block, restart_period)`` (the same reinterpretation
  :class:`~repro.tune.table.TableEntry` records and
  ``SolverSpec.resolve_for_shape`` reads back), so the tuner, table
  and resolution stay schema-compatible across backends.

Everything returned here is safe to *run*; which candidate is fastest
is the runner's job to measure, never this module's to guess.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

from repro.kernels.batch_lp import DEFAULT_TILE, LANE, _pick_tile
from repro.solver.spec import DTYPES, RGB_DEFAULT_TILE, jnp_itemsize
from repro.tune.table import current_device_kind, device_platform

RGB_TILES = (8, 16, 32, 64, 128, 256)
RGB_CHUNKS = (0, 64, 128)
KERNEL_TILES = (8, 16, 32, 64, 128)
KERNEL_CHUNKS = (0, 128, 256)
# pdhg iteration schedule, riding in the (tile, chunk) slots.
PDHG_ITER_BLOCKS = (32, 64, 128)
PDHG_RESTART_PERIODS = (0, 512, 2048)


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One tunable configuration (tile/chunk are concrete, never None).

    For ``backend="pdhg"`` the slots carry ``(iter_block,
    restart_period)`` instead of launch geometry."""

    backend: str
    tile: int
    chunk: int

    def label(self) -> str:
        if self.backend == "pdhg":
            return f"pdhg/ib{self.tile}/rp{self.chunk}"
        return f"{self.backend}/t{self.tile}/c{self.chunk}"


def default_backends(device_kind: Optional[str] = None) -> tuple:
    """Backends worth timing on a device family: the Pallas kernel only
    runs compiled on TPU (interpret mode measures the emulator, not the
    hardware), the dense pair runs everywhere, and pdhg is the
    large-m first-order contender on every platform."""
    kind = device_kind if device_kind is not None else current_device_kind()
    if device_platform(kind) == "tpu":
        return ("rgb", "kernel", "pdhg")
    return ("naive", "rgb", "pdhg")


def candidate_space(
    m_pad: int,
    batch: int,
    *,
    dtype: str = "float32",
    device_kind: Optional[str] = None,
    backends: Optional[Sequence[str]] = None,
) -> List[Candidate]:
    """All valid candidates for one shape class, deterministic order."""
    if m_pad < 1 or batch < 1:
        raise ValueError(f"need m_pad >= 1 and batch >= 1, got "
                         f"({m_pad}, {batch})")
    if dtype not in DTYPES:
        raise ValueError(f"dtype={dtype!r}; expected one of {DTYPES}")
    itemsize = jnp_itemsize(dtype)
    if backends is None:
        backends = default_backends(device_kind)
    batch_cap = max(8, -(-batch // 8) * 8)  # sublane-rounded batch
    out: List[Candidate] = []
    for backend in backends:
        if backend == "naive":
            out.append(Candidate("naive", RGB_DEFAULT_TILE, 0))
        elif backend == "rgb":
            for tile in RGB_TILES:
                if tile > batch_cap and tile != RGB_TILES[0]:
                    continue  # keep one rung even for tiny batches
                for chunk in RGB_CHUNKS:
                    if chunk and chunk >= m_pad:
                        continue
                    out.append(Candidate("rgb", tile, chunk))
        elif backend == "kernel":
            m_lane = -(-m_pad // LANE) * LANE
            # largest VMEM-feasible tile for this shape/dtype
            t_max = _pick_tile(m_lane, None, itemsize=itemsize)
            for tile in KERNEL_TILES:
                if tile > min(t_max, DEFAULT_TILE, batch_cap) \
                        and tile != KERNEL_TILES[0]:
                    continue
                for chunk in KERNEL_CHUNKS:
                    if chunk and (chunk >= m_lane or m_lane % chunk):
                        continue
                    out.append(Candidate("kernel", tile, chunk))
        elif backend == "pdhg":
            for iter_block in PDHG_ITER_BLOCKS:
                for period in PDHG_RESTART_PERIODS:
                    if period and period < iter_block:
                        continue  # a period under one block never fires
                    out.append(Candidate("pdhg", iter_block, period))
        else:
            raise ValueError(f"unknown backend {backend!r}")
    return out
