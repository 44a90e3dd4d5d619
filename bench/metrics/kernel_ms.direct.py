"""Device time of the LP kernel per solve call, from the trace."""


def read(ctx):
    calls = ctx["counters"]["calls"]
    if not calls or ctx["trace"].kernel_s <= 0:
        return None
    return 1e3 * ctx["trace"].kernel_s / calls
