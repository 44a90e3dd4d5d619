"""Rows the busiest chip solved over the mean per chip, from the
program's ``rows_per_device`` counts of the window."""


def read(ctx):
    rows = list(ctx["counters"].get("rows_per_device") or [])
    rows += [0] * (ctx["n_devices"] - len(rows))
    if not rows or sum(rows) == 0:
        return None
    return max(rows) / (sum(rows) / len(rows))
