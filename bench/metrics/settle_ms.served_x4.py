"""Host time per ``serve.settle`` span in the traced window: each
flush's completion from its results on the host to its last future
settled (buffers returned, metrics recorded, results built, callbacks
run; ``bench.host_spans``)."""
from bench.host_spans import span_ms


def read(ctx):
    return span_ms(ctx, "serve.settle")
