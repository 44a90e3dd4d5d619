"""The comparison that decides ``correct``.

The program's answers for a sample of the LPs it solved in the window
are judged against the plain reference (``bench.reference``) in
float64.  The configuration states a feasibility tolerance ``eps``
(on unit normals), so the reference solves each LP twice: with every
offset relaxed by ``eps`` and tightened by ``eps``.  Two numbers are
compared:

- ``flags_wrong``: LPs the program calls infeasible although the
  tightened LP is feasible, or feasible although even the relaxed LP is
  not.  An LP feasible only within the tolerance (the ``degenerate``
  requests, whose constraints all pass through one point) may be called
  either.  Exact: its limit is 0.
- ``obj_gap``: the widest gap |c.x - c.x_ref| / (1 + |c.x_ref|) between
  the program's objective and the relaxed reference's optimum, over the
  LPs both call feasible.
"""
from __future__ import annotations

import numpy as np

from bench import reference


def readings(A, b, c, m_valid, x, feasible, *, M: float, eps: float,
             dtype=np.float64) -> dict:
    """``flags_wrong`` and ``obj_gap`` of answers ``(x, feasible)`` for one
    group of LPs of common padded width.  ``dtype`` is the reference's
    arithmetic (float64; the control check passes answers computed in a
    narrower type as ``x, feasible`` instead)."""
    x_ref, f_relaxed = reference.solve(A, b, c, m_valid, M=M, eps=eps,
                                       dtype=dtype)
    _, f_tight = reference.solve(A, b, c, m_valid, M=M, eps=-eps,
                                 dtype=dtype)
    feasible = np.asarray(feasible, bool).reshape(-1)
    wrong = (feasible & ~f_relaxed) | (~feasible & f_tight)
    both = feasible & f_relaxed
    c64 = np.asarray(c, np.float64)
    obj = np.einsum("kd,kd->k", c64, np.asarray(x, np.float64))
    ref = np.einsum("kd,kd->k", c64, x_ref)
    gap = np.abs(obj - ref) / (1.0 + np.abs(ref))
    gap = np.where(both, gap, 0.0)
    # A non-finite answer to an LP both call feasible is as wrong as can be.
    gap = np.where(both & ~np.isfinite(gap), np.inf, gap)
    return {"flags_wrong": int(wrong.sum()),
            "obj_gap": float(np.max(gap, initial=0.0)),
            "compared": int(len(feasible))}


def judge(sample, config: dict) -> dict:
    """Readings of a driver's sample: groups of LPs with the program's
    answers ``x`` and ``feasible``."""
    return merge(readings(g["A"], g["b"], g["c"], g["m_valid"], g["x"],
                          g["feasible"], M=float(config["M"]),
                          eps=float(config["feasibility_tolerance"]))
                 for g in sample)


def merge(parts) -> dict:
    """Readings of several groups as one."""
    parts = list(parts)
    return {"flags_wrong": sum(p["flags_wrong"] for p in parts),
            "obj_gap": max((p["obj_gap"] for p in parts), default=0.0),
            "compared": sum(p["compared"] for p in parts)}


def verdict(read: dict, limits: dict, failed: int) -> tuple:
    """``(correct, checks)``: each number compared beside its limit, in
    the order printed.  Requests that failed or never came count too."""
    checks = {
        "failed": {"value": failed, "limit": 0},
        "flags_wrong": {"value": read["flags_wrong"],
                        "limit": limits["flags_wrong"]},
        "obj_gap": {"value": read["obj_gap"], "limit": limits["obj_gap"]},
    }
    ok = read["compared"] > 0 and all(
        ch["value"] <= ch["limit"] for ch in checks.values())
    return ok, checks
