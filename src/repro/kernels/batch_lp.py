"""Pallas TPU kernel for the RGB batch 2-D LP solver.

TPU-native realisation of the paper's cooperative-thread-array design:

* One **grid step** owns a tile of ``T`` problems (the thread-block
  analogue).  Constraints are stored struct-of-arrays, packed
  ``L[b, 0:3, h] = (a_x, a_y, b)`` with the constraint index ``h`` on the
  **128-lane minor axis** — the paper's "combining the information into one
  extended set of data ensures scattered reads use as much of each cache
  line as possible", except here every load is a full (8, 128) VMEM tile.
* Seidel's loop keeps a **position per problem**, not one for the tile.
  A constraint that the current optimum satisfies changes nothing, so
  each problem jumps straight to its next violated constraint: one dense
  pass over its lanes tests ``a_h @ x > b_h + EPS_FEAS`` for every
  ``h`` from its position on, and a masked lane min picks the first.
  The loop then runs once per violation, not once per constraint: a
  tile takes its busiest problem's re-solve count + 1 iterations, and
  stops when no problem in it has a violation left.  Under a random
  order a problem makes about 2 ln m re-solves (7 at m 64, 13 at m 1024
  on the paper's generator) and the busiest of 128 up to twice that.
  A problem that turns out infeasible stops there, with ``x`` as that
  step left it.
* The O(i) re-solve **work units** (one 1-D intersection per prior
  constraint) execute as dense vector ops along the lane axis, each
  problem masked to the constraints before its own position; the paper's
  shared-memory ``atomicMin``/``atomicMax`` accumulation of
  u_left/u_right becomes a masked lane **min/max reduction** (TPUs have
  no atomics; a reduction tree is the idiomatic equivalent and is
  contention-free).  With ``chunk`` the re-solve reads only the lane
  blocks below the tile's furthest position.
* Tiles only pay for their own violations, so a tile of small or easy
  LPs finishes early even when another tile carries large ones — the
  paper's "offloading work units of larger problems onto threads which
  are computing smaller problems".

The order of the constraints sets the cost.  An order in which almost
every constraint is violated when it is reached (constraints sorted so,
solved with ``SolverSpec(shuffle=False)``) takes m iterations, as a loop
over every constraint would, and each also pays for the scan;
``SolverSpec(shuffle=True)`` restores the expected 2 ln m.  The answer
does not depend on the loop's shape: skipping a constraint that is not
violated is what Seidel's method does anyway.

All per-problem scalars are kept as (T, 1) so every intermediate is >= 2-D
(Mosaic requires >= 2-D iota / layouts).  Constraints are read straight
from ``L_ref`` (Mosaic has no ``dynamic_slice`` of a value), a problem's
own constraint by a masked max over the lanes, and flags, positions and
counts are int32 (Mosaic cannot carry, reduce or select i1 vectors).  The
kernel is validated in ``interpret=True`` mode on CPU against
``kernels.ref`` and scipy, and compiled for a described v5e in
``tests/test_tpu_compile.py``.
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
# fewer; the dense count bounds both.  The violation scan and the row
# read fold 128-lane blocks, so they add no (T, m_pad) temporary.
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


def _rgb_kernel(L_ref, c_ref, mv_ref, x_ref, feas_ref, n_ref, *, M: float,
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
    # Constants are typed explicitly: under x64 a Python scalar handed
    # to a jnp function becomes an i64/f64 convert, which Mosaic
    # cannot lower.
    one, zero = jnp.int32(1), jnp.int32(0)
    done = jnp.int32(m_pad)   # position sentinel: no violation left
    neg_inf = jnp.asarray(-jnp.inf, dt)

    def _sign_tb(v, tb):
        return jnp.where(jnp.abs(v) > oneD.EPS_TIE, jnp.sign(v),
                         jnp.where(jnp.abs(tb) > oneD.EPS_TIE,
                                   jnp.sign(tb), jnp.ones((), dt)))

    def _fold_blocks(fn, init):
        """Fold ``fn(lanes, load, acc)`` over the 128-lane blocks:
        ``lanes`` is the block's (T, LANE) constraint index and
        ``load(k)`` loads its component ``k``.  The accumulators
        are (T, LANE), so no (T, m_pad) temporary is ever live; the
        caller reduces them across lanes once."""
        def step(j, acc):
            base = pl.multiple_of(j * LANE, LANE)
            lanes = base + jax.lax.broadcasted_iota(jnp.int32, (T, LANE), 1)
            return fn(lanes, lambda k: L_ref[:, k, pl.ds(base, LANE)], acc)

        return jax.lax.fori_loop(zero, jnp.int32(m_pad // LANE), step, init)

    def _next_violation(x, start):
        """Per problem, the first constraint at or after ``start`` that
        ``x`` violates, or ``done``: one pass over the lanes and a
        masked int32 lane min.  The test is the one Seidel's step
        makes, lane by lane."""
        def fn(lanes, load, first):
            lhs = load(0) * x[:, 0:1] + load(1) * x[:, 1:2]
            viol = ((lanes >= start) & (lanes < mv)
                    & (lhs > load(2) + oneD.EPS_FEAS))
            return jnp.minimum(first, jnp.where(viol, lanes, done))

        first = _fold_blocks(fn, jnp.full((T, LANE), done))
        return jnp.min(first, axis=1, keepdims=True)

    def _row(i):
        """Each problem's constraint ``i`` as three (T, 1) components,
        by a masked max over the lanes (exact: max(v, -inf) == v, sign
        of zero kept)."""
        def fn(lanes, load, acc):
            at = lanes == i
            return tuple(jnp.maximum(a, jnp.where(at, load(k), neg_inf))
                         for k, a in enumerate(acc))

        acc = _fold_blocks(fn, (jnp.full((T, LANE), neg_inf, dt),) * 3)
        return tuple(jnp.max(a, axis=1, keepdims=True) for a in acc)

    def resolve(x, i):
        """Seidel's re-solve of each problem on its constraint ``i``
        ((T, 1) positions): the 1-D LP along that line over the
        constraints before it and the box."""
        a_ix, a_iy, b_i = _row(i)
        # Line frame: p0 = a_i * b_i (unit normals), u = perp(a_i).
        p0x, p0y = a_ix * b_i, a_iy * b_i
        ux, uy = -a_iy, a_ix

        def _bounds_block(start, width):
            """sigma bounds over lanes [start, start + width) (paper
            eqs. 3-4); the min/max is the atomicMin/atomicMax analogue.
            ``bad`` is int32 0/1: Mosaic has no i1 vectors in
            reductions or loop carries."""
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
            # chunked re-solve: only the lane blocks below the furthest
            # position re-solved in the tile (work proportional to i,
            # the true WU count)
            def blk(j, carry):
                t_lo, t_hi, bad = carry
                lo_j, hi_j, bad_j = _bounds_block(
                    pl.multiple_of(j * chunk, chunk), chunk)
                return (jnp.maximum(t_lo, lo_j),
                        jnp.minimum(t_hi, hi_j),
                        jnp.maximum(bad, bad_j))

            i_max = jnp.max(jnp.where(i < done, i, zero))
            n_blocks = jax.lax.div(i_max + (chunk - 1), jnp.int32(chunk))
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
        # written with & / | because Mosaic cannot select between i1
        # vectors.
        cu = cx * ux + cy * uy
        cpu = cpx * ux + cpy * uy
        tie = jnp.abs(cu) <= oneD.EPS_TIE
        pick_hi = (~tie & (cu > 0.0)) | (tie & (cpu > 0.0))
        tt = jnp.where(pick_hi, t_hi, t_lo)
        x_new = jnp.concatenate([p0x + tt * ux, p0y + tt * uy], axis=1)
        return x_new, feas_new

    x0 = jnp.concatenate(
        [_sign_tb(cx, cpx) * Mv, _sign_tb(cy, cpy) * Mv], axis=1)  # (T, 2)
    feas0 = jnp.ones((T, 1), jnp.int32)
    nxt0 = _next_violation(x0, jnp.zeros((T, 1), jnp.int32))
    n0 = jnp.zeros((T, 1), jnp.int32)

    def cond(carry):
        # An int32 reduction, not jnp.any: Mosaic reduces bools through
        # a float of the default width, which is f64 under x64.
        return jnp.min(carry[2]) < done

    def body(carry):
        x, feas, nxt, n = carry
        # Problems with no violation left (``nxt == done``) re-solve
        # too, on garbage, and keep what they had.
        active = nxt < done
        x_new, feas_new = resolve(x, nxt)
        x = jnp.where(active, x_new, x)
        feas = jnp.where(active, feas_new, feas)
        n = n + jnp.where(active, one, zero)
        # An infeasible problem is finished: x stays as the step that
        # found it left it.
        start = jnp.where(active & (feas > 0), nxt + one, done)
        return x, feas, _next_violation(x, start), n

    x, feas, _, n = jax.lax.while_loop(cond, body, (x0, feas0, nxt0, n0))
    x_ref[...] = x
    feas_ref[...] = feas
    n_ref[...] = n


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
    multiple of 128 (handled by kernels.ops).  Returns ``x (B, 2)``,
    ``feasible (B, 1)`` int32 and each problem's re-solve count
    ``(B, 1)`` int32 (its tile's loop ran the tile's largest count + 1
    scans)."""
    B, _, m_pad = L.shape
    T = tile or _pick_tile(m_pad, B, itemsize=L.dtype.itemsize)
    if B % T:
        raise ValueError(f"batch {B} not a multiple of tile {T}")
    if m_pad % LANE:
        raise ValueError(f"m_pad {m_pad} not a multiple of {LANE}")
    grid = (B // T,)
    # Per problem: a re-solve is ~12 flops a lane, the row read ~6 and
    # a scan ~10; every re-solve is followed by a scan, and one scan
    # starts the loop.  Under a random order constraint i is violated
    # with probability at most 2/i: about 2 H_m re-solves.
    flops_resolve, flops_scan = 18 * m_pad, 10 * m_pad
    n_resolves = 2.0 * sum(1.0 / k for k in range(1, m_pad + 1))
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

    x, feas, resolves = pl.pallas_call(
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
            pl.BlockSpec((T, 1), rows2),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 2), L.dtype),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=int(B * (n_resolves * (flops_resolve + flops_scan)
                           + flops_scan)),
            bytes_accessed=L.size * L.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
        name="rgb_lp",
    )(L, c, m_valid)
    return x, feas, resolves
