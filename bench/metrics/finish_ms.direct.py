"""Device time per solve call of the ops under the ``solver.finish``
scope: slicing the padding off, the flag cast and the objective
(``bench.scopes``)."""
from bench.scopes import stage_ms


def read(ctx):
    return stage_ms(ctx, "solver.finish")
