"""Host time per ``serve.assemble`` span in the traced window: each
flush's assembly (queue waits recorded, buffers leased and filled, the
executable fetched), dispatch's backpressure outside it
(``bench.host_spans``)."""
from bench.host_spans import span_ms


def read(ctx):
    return span_ms(ctx, "serve.assemble")
