"""Device time per solve call of the ops under the ``solver.pad`` scope:
padding the constraint axis to a lane multiple and the batch to a tile
multiple (``bench.scopes``)."""
from bench.scopes import stage_ms


def read(ctx):
    return stage_ms(ctx, "solver.pad")
