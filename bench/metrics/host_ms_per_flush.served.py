"""Host time per flush from the start of its assembly to the return of
its dispatch, backpressure included: the program's ``assemble_seconds``
counter over its flushes in the window."""


def read(ctx):
    n = ctx["counters"].get("n_flushes")
    if not n:
        return None
    return 1e3 * ctx["counters"]["assemble_s"] / n
