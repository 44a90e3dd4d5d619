"""Pallas TPU kernel for the RGB batch 2-D LP solver.

TPU-native realisation of the paper's cooperative-thread-array design
(DESIGN.md section 2):

* One **grid step** owns a tile of ``T`` problems (the thread-block
  analogue).  Constraints are stored struct-of-arrays, packed
  ``L[b, 0:3, h] = (a_x, a_y, b)`` with the constraint index ``h`` on the
  **128-lane minor axis** — the paper's "combining the information into one
  extended set of data ensures scattered reads use as much of each cache
  line as possible", except here every load is a full (8, 128) VMEM tile.
* The O(i) re-solve **work units** (one 1-D intersection per prior
  constraint) execute as dense vector ops along the lane axis; the paper's
  shared-memory ``atomicMin``/``atomicMax`` accumulation of u_left/u_right
  becomes a masked lane **min/max reduction** (TPUs have no atomics; a
  reduction tree is the idiomatic equivalent and is contention-free).
* A scalar-predicate ``lax.cond`` skips the whole re-solve when no problem
  in the tile is violated at step i — the block-level early exit that makes
  randomised constraint order pay (expected O(1) violations per problem).
* The iteration count is ``max(m_valid)`` over the tile (dynamic
  ``while_loop``), so a tile of small LPs finishes early even when another
  tile carries large LPs — the paper's "offloading work units of larger
  problems onto threads which are computing smaller problems" becomes
  "tiles only pay for their own largest problem".

All per-problem scalars are kept as (T, 1) so every intermediate is >= 2-D
(Mosaic requires >= 2-D iota / layouts).  Constraints are read straight
from ``L_ref`` with aligned ``pl.ds`` lane windows (Mosaic has no
``dynamic_slice`` of a value), and flags are int32 (Mosaic cannot carry,
reduce or select i1 vectors).  The kernel is validated in
``interpret=True`` mode on CPU against ``kernels.ref`` and scipy, and
compiled for a described v5e in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import oneD

DEFAULT_TILE = 128
LANE = 128


# Scoped VMEM a grid step may use.  v5e's scoped default is 16 MiB (of
# 128 MiB physical); 12 MiB leaves room for the fixed ~36 KiB of small
# blocks and for compiler scratch the model below does not count.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024
# Words per constraint lane per problem, as the TPU compiler allocates
# them (its scoped-VMEM reports for m_pad 2048-8192, T 40-160): the
# (4, m_pad) block double-buffered by the grid pipeline (2 x 4, no
# sublane padding), plus ~6 (T, m_pad) temporaries of the dense
# re-solve (denom, num, t, lo/hi selects).  The chunked re-solve needs
# fewer; the dense count bounds both.
_WORDS_PER_LANE = 2 * 4 + 6


def _pick_tile(m_pad: int, batch: int | None = None,
               itemsize: int = 4) -> int:
    """Choose the batch tile so one grid step's VMEM working set fits
    ``VMEM_BUDGET_BYTES``: ``_WORDS_PER_LANE * m_pad`` words per problem
    at the solve dtype's ``itemsize`` (float64 halves the tile).  T stays
    a multiple of 8 (sublanes) and, when the batch size is known, is
    clamped to ceil(batch/8)*8 so a small batch is not padded all the
    way up to DEFAULT_TILE."""
    bytes_per_problem = _WORDS_PER_LANE * m_pad * itemsize
    t = VMEM_BUDGET_BYTES // bytes_per_problem
    t = max(8, min(DEFAULT_TILE, (t // 8) * 8))
    if batch is not None:
        t = min(t, max(8, -(-batch // 8) * 8))
    return t


def _rgb_kernel(L_ref, c_ref, mv_ref, x_ref, feas_ref, *, M: float,
                chunk: int = 0):
    c = c_ref[...]            # (T, 2)
    mv = mv_ref[...]          # (T, 1) int32
    T, _, m_pad = L_ref.shape
    dt = L_ref.dtype

    cx = c[:, 0:1]            # (T, 1)
    cy = c[:, 1:2]
    cpx, cpy = -cy, cx        # perpendicular (tie-break) objective

    big = jnp.asarray(jnp.finfo(dt).max, dt)
    Mv = jnp.asarray(M, dt)
    lane = jax.lax.broadcasted_iota(jnp.int32, (T, LANE), 1)
    # Constants are typed explicitly: under x64 a Python scalar handed
    # to a jnp function becomes an i64/f64 convert, which Mosaic
    # cannot lower.
    one, zero, lane_n = jnp.int32(1), jnp.int32(0), jnp.int32(LANE)
    neg_inf = jnp.asarray(-jnp.inf, dt)

    def _sign_tb(v, tb):
        return jnp.where(jnp.abs(v) > oneD.EPS_TIE, jnp.sign(v),
                         jnp.where(jnp.abs(tb) > oneD.EPS_TIE,
                                   jnp.sign(tb), jnp.ones((), dt)))

    def _row_i(k, i):
        """Component ``k`` of constraint ``i`` as (T, 1): load the
        aligned 128-lane block holding ``i`` and pick lane ``i`` with a
        masked max (exact: max(v, -inf) == v, sign of zero kept)."""
        base = pl.multiple_of(jax.lax.div(i, lane_n) * lane_n, LANE)
        blk = L_ref[:, k, pl.ds(base, LANE)]
        return jnp.max(jnp.where(lane == i - base, blk, neg_inf),
                       axis=1, keepdims=True)

    x0 = jnp.concatenate(
        [_sign_tb(cx, cpx) * Mv, _sign_tb(cy, cpy) * Mv], axis=1)  # (T, 2)
    feas0 = jnp.ones((T, 1), jnp.int32)
    max_mv = jnp.max(mv)

    def cond(carry):
        i, _, _ = carry
        return i < max_mv

    def body(carry):
        i, x, feas = carry
        a_ix, a_iy, b_i = _row_i(0, i), _row_i(1, i), _row_i(2, i)
        lhs = a_ix * x[:, 0:1] + a_iy * x[:, 1:2]
        violated = (feas > 0) & (i < mv) & (lhs > b_i + oneD.EPS_FEAS)

        def resolve(x):
            # Line frame: p0 = a_i * b_i (unit normals), u = perp(a_i).
            p0x, p0y = a_ix * b_i, a_iy * b_i
            ux, uy = -a_iy, a_ix

            def _bounds_block(start, width):
                """sigma bounds over lanes [start, start + width) (paper
                eqs. 3-4); the min/max is the atomicMin/atomicMax
                analogue.  ``bad`` is int32 0/1: Mosaic has no i1
                vectors in reductions or loop carries."""
                axc = L_ref[:, 0, pl.ds(start, width)]
                ayc = L_ref[:, 1, pl.ds(start, width)]
                bbc = L_ref[:, 2, pl.ds(start, width)]
                iota_c = start + jax.lax.broadcasted_iota(
                    jnp.int32, (T, width), 1)
                denom = axc * ux + ayc * uy
                num = bbc - (axc * p0x + ayc * p0y)
                is_par = jnp.abs(denom) <= oneD.EPS_DENOM
                t = num / jnp.where(is_par, jnp.ones((), dt), denom)
                mask = iota_c < i
                hi = jnp.where(mask & (denom > oneD.EPS_DENOM), t, big)
                lo = jnp.where(mask & (denom < -oneD.EPS_DENOM), t, -big)
                bad = jnp.max(jnp.where(
                    mask & is_par & (num < -oneD.EPS_FEAS), one, zero),
                    axis=1, keepdims=True)
                return (jnp.max(lo, axis=1, keepdims=True),
                        jnp.min(hi, axis=1, keepdims=True), bad)

            if chunk:
                # chunked re-solve: only ceil(i/chunk) lane blocks of WUs
                # (work proportional to i, the true WU count)
                def blk(j, carry):
                    t_lo, t_hi, bad = carry
                    lo_j, hi_j, bad_j = _bounds_block(
                        pl.multiple_of(j * chunk, chunk), chunk)
                    return (jnp.maximum(t_lo, lo_j),
                            jnp.minimum(t_hi, hi_j),
                            jnp.maximum(bad, bad_j))

                n_blocks = jax.lax.div(i + (chunk - 1), jnp.int32(chunk))
                t_lo, t_hi, par_bad = jax.lax.fori_loop(
                    zero, n_blocks, blk,
                    (jnp.full((T, 1), -big, dt), jnp.full((T, 1), big, dt),
                     jnp.zeros((T, 1), jnp.int32)))
            else:
                t_lo, t_hi, par_bad = _bounds_block(0, m_pad)
            # --- The four box bounds, computed in closed form ---
            for bd, bn in (
                (ux, Mv - p0x), (-ux, Mv + p0x),
                (uy, Mv - p0y), (-uy, Mv + p0y),
            ):
                t_hi = jnp.minimum(
                    t_hi, jnp.where(bd > oneD.EPS_DENOM, bn / bd, big))
                t_lo = jnp.maximum(
                    t_lo, jnp.where(bd < -oneD.EPS_DENOM, bn / bd, -big))
                par_bad = jnp.where(
                    (jnp.abs(bd) <= oneD.EPS_DENOM) & (bn < -oneD.EPS_FEAS),
                    one, par_bad)
            feas_new = jnp.where((t_lo <= t_hi + oneD.EPS_FEAS)
                                 & (par_bad == 0), one, zero)
            # Objective endpoint selection (tie -> perpendicular objective),
            # written with & / | because Mosaic cannot select between
            # i1 vectors.
            cu = cx * ux + cy * uy
            cpu = cpx * ux + cpy * uy
            tie = jnp.abs(cu) <= oneD.EPS_TIE
            pick_hi = (~tie & (cu > 0.0)) | (tie & (cpu > 0.0))
            tt = jnp.where(pick_hi, t_hi, t_lo)
            x_new = jnp.concatenate([p0x + tt * ux, p0y + tt * uy], axis=1)
            return x_new, feas_new

        # The branches return candidates; the select over ``violated``
        # stays outside the cond so no i1 vector crosses its boundary.
        # An int32 max, not jnp.any: Mosaic reduces bools through a
        # float of the default width, which is f64 under x64.
        any_violated = jnp.max(jnp.where(violated, one, zero)) > 0
        x_new, feas_new = jax.lax.cond(
            any_violated, resolve, lambda x: (x, feas), x)
        x = jnp.where(violated, x_new, x)
        feas = jnp.where(violated, feas_new, feas)
        return i + 1, x, feas

    _, x, feas = jax.lax.while_loop(cond, body, (jnp.int32(0), x0, feas0))
    x_ref[...] = x
    feas_ref[...] = feas


def rgb_pallas(
    L: jax.Array,        # (B, 4, m_pad) packed constraints, unit normals
    c: jax.Array,        # (B, 2)
    m_valid: jax.Array,  # (B, 1) int32
    *,
    M: float,
    tile: int | None = None,
    chunk: int = 0,      # 0 = dense re-solve; 128 = lane-width chunks
    interpret: bool = False,
):
    """Launch the RGB kernel.  B must be a multiple of the tile and m_pad a
    multiple of 128 (handled by kernels.ops)."""
    B, _, m_pad = L.shape
    T = tile or _pick_tile(m_pad, B, itemsize=L.dtype.itemsize)
    if B % T:
        raise ValueError(f"batch {B} not a multiple of tile {T}")
    if m_pad % LANE:
        raise ValueError(f"m_pad {m_pad} not a multiple of {LANE}")
    grid = (B // T,)
    flops_resolve = 12 * m_pad  # per problem per violation, approx
    if chunk and (m_pad % chunk or chunk % LANE):
        raise ValueError(f"chunk {chunk} must be a multiple of {LANE} "
                         f"dividing m_pad {m_pad}")
    kernel = functools.partial(_rgb_kernel, M=M, chunk=chunk)

    # Index maps return typed zeros: a bare 0 is i64 under x64, and
    # Mosaic cannot return it.
    def rows3(t):
        return t, jnp.int32(0), jnp.int32(0)

    def rows2(t):
        return t, jnp.int32(0)

    x, feas = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((T, 4, m_pad), rows3),
            pl.BlockSpec((T, 2), rows2),
            pl.BlockSpec((T, 1), rows2),
        ],
        out_specs=[
            pl.BlockSpec((T, 2), rows2),
            pl.BlockSpec((T, 1), rows2),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 2), L.dtype),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=B * flops_resolve * 2,  # ~2 ln m expected violations
            bytes_accessed=L.size * L.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
        name="rgb_lp",
    )(L, c, m_valid)
    return x, feas
