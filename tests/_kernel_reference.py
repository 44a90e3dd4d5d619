"""The LP kernel as it stood before its loop jumped each problem to its
next violated constraint: one ``while_loop`` step per constraint index
for the whole tile, a tile-wide ``lax.cond`` around the re-solve.
Tests hold today's kernel bit-identical to it in interpret mode."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import oneD
from repro.kernels.batch_lp import LANE, _pick_tile


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


def reference_rgb_pallas(L, c, m_valid, *, M: float,
                         tile: int | None = None, chunk: int = 0):
    """Run the old kernel in interpret mode: ``(x (B, 2), feas (B, 1))``."""
    B, _, m_pad = L.shape
    T = tile or _pick_tile(m_pad, B, itemsize=L.dtype.itemsize)

    def rows3(t):
        return t, jnp.int32(0), jnp.int32(0)

    def rows2(t):
        return t, jnp.int32(0)

    return pl.pallas_call(
        functools.partial(_rgb_kernel, M=M, chunk=chunk),
        grid=(B // T,),
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
        interpret=True,
    )(L, c, m_valid)
