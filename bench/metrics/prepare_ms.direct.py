"""Device time per solve call of the ops under the ``solver.prepare``
scope: cast, normalise, shuffle and pack (``bench.scopes``)."""
from bench.scopes import stage_ms


def read(ctx):
    return stage_ms(ctx, "solver.prepare")
