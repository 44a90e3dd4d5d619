"""Device time per solve call of every operation other than the LP
kernel: the solver front end's cast, normalise, pack and pad, and the
objective."""


def read(ctx):
    calls = ctx["counters"]["calls"]
    if not calls or ctx["trace"].ops_s <= 0:
        return None
    return 1e3 * (ctx["trace"].ops_s - ctx["trace"].kernel_s) / calls
