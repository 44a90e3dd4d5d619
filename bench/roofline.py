"""The bytes the LP kernel must move, from the work it was given.

The kernel reads three float32 words per valid constraint (a_x, a_y, b)
and each problem's objective (2 words), and writes x (2 words) and a
feasibility flag (1 word).  Padding is not counted, so the least time
is the same whatever implements the solve.  The kernel does no matrix
multiply and v5e publishes no float32 vector peak, so its bound is HBM
bandwidth: least time = bytes / peak bytes per second.
"""
from __future__ import annotations

WORD = 4


def kernel_bytes(valid_constraints: int, problems: int) -> int:
    """Bytes the algorithm must move for ``problems`` LPs holding
    ``valid_constraints`` constraints in all."""
    return WORD * (3 * valid_constraints + 5 * problems)


def roofline_share(nbytes: float, kernel_s: float, hbm_bytes_per_s: float):
    """Percent of the HBM roofline: least time over the kernel's device
    time; ``None`` where the kernel never ran."""
    if kernel_s <= 0:
        return None
    return 100.0 * (nbytes / hbm_bytes_per_s) / kernel_s


def read(ctx):
    """The per-layer reader of a kernel's roofline share: the bytes its
    work needs (``counters["kernel_bytes"]``) at the chip's peak
    bandwidth, over the kernel's device time in the trace; ``None``
    where the kernel never ran."""
    if ctx["trace"].kernel_s <= 0:
        return None
    return roofline_share(ctx["counters"]["kernel_bytes"],
                          ctx["trace"].kernel_s,
                          ctx["peaks"]["hbm_bytes_per_s"])
