"""Plain reference of the solver's semantics, independent of the program.

maximize c.x subject to a_h.x <= b_h for the valid rows h, inside the
box |x|, |y| <= M.  Constraints are scaled to unit normals, and a
constraint is violated when a.x exceeds b by more than ``eps``: the
tolerance the configuration states: the reference solves the LP with
every offset relaxed by ``eps``, exactly in its working precision, so a
point that the tolerance admits is feasible.  Seidel's incremental
algorithm in the order given, vectorised over a group of LPs with numpy:
when the running optimum violates constraint i, the new optimum is the
best point of line i that the box and constraints 0..i-1 allow, or none.

``dtype`` is the arithmetic: float64 for the reference, a narrower type
(``ml_dtypes.bfloat16``) for the control.
"""
from __future__ import annotations

import numpy as np


def solve(A, b, c, m_valid, *, M: float, eps: float, dtype=np.float64):
    """Solve K LPs of padded width m.  ``A (K, m, 2)``, ``b (K, m)``,
    ``c (K, 2)``, ``m_valid (K,)``.  Returns ``(x (K, 2) float64,
    feasible (K,) bool)``; x is meaningless where infeasible."""
    dt = np.dtype(dtype)
    A = np.asarray(A).astype(dt)
    b = np.asarray(b).astype(dt)
    c = np.asarray(c).astype(dt)
    mv = np.asarray(m_valid).reshape(-1)
    K, m = b.shape
    one = dt.type(1)
    norm = np.sqrt(A[..., 0] * A[..., 0] + A[..., 1] * A[..., 1])
    norm = np.where(norm > 0, norm, one)
    ax, ay = A[..., 0] / norm, A[..., 1] / norm
    bb = b / norm + dt.type(eps)
    box_ax = np.array([1, -1, 0, 0], dt)
    box_ay = np.array([0, 0, 1, -1], dt)
    box_b = np.full(4, M, dt)
    Mv, zero = dt.type(M), dt.type(0)
    # start at the box corner the objective prefers
    x = np.stack([np.where(c[:, 0] >= 0, Mv, -Mv),
                  np.where(c[:, 1] >= 0, Mv, -Mv)], axis=1)
    feas = np.ones(K, bool)
    for i in range(m):
        act = feas & (i < mv)
        if not act.any():
            continue
        lhs = ax[:, i] * x[:, 0] + ay[:, i] * x[:, 1]
        v = np.nonzero(act & (lhs > bb[:, i]))[0]
        if v.size == 0:
            continue
        aix, aiy, bi = ax[v, i], ay[v, i], bb[v, i]
        p0x, p0y = aix * bi, aiy * bi
        ux, uy = -aiy, aix
        px = np.concatenate([np.broadcast_to(box_ax, (v.size, 4)),
                             ax[v, :i]], axis=1)
        py = np.concatenate([np.broadcast_to(box_ay, (v.size, 4)),
                             ay[v, :i]], axis=1)
        pb = np.concatenate([np.broadcast_to(box_b, (v.size, 4)),
                             bb[v, :i]], axis=1)
        denom = px * ux[:, None] + py * uy[:, None]
        num = pb - (px * p0x[:, None] + py * p0y[:, None])
        pos, neg = denom > zero, denom < zero
        safe = np.where(pos | neg, denom, one)
        t = num / safe
        inf = dt.type(np.inf)
        t_hi = np.min(np.where(pos, t, inf), axis=1)
        t_lo = np.max(np.where(neg, t, -inf), axis=1)
        par_bad = np.any(~pos & ~neg & (num < zero), axis=1)
        ok = (t_lo <= t_hi) & ~par_bad
        cu = c[v, 0] * ux + c[v, 1] * uy
        tt = np.where(cu >= zero, t_hi, t_lo)
        x[v, 0] = np.where(ok, p0x + tt * ux, x[v, 0])
        x[v, 1] = np.where(ok, p0y + tt * uy, x[v, 1])
        feas[v] = ok
    return x.astype(np.float64), feas
