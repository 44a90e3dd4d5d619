"""Valid constraint cells over launched cells in the window's flushes
(1 - ``padding_waste_cells`` of the program's ServeMetrics)."""


def read(ctx):
    if not ctx["counters"].get("n_flushes"):
        return None
    return 100.0 * (1.0 - ctx["counters"]["padding_waste_cells"])
