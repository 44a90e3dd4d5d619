"""Mean time a request waited in the scheduler's queue, from its submit
to its flush's assembly: the program's ``queue_wait_seconds`` counter
(sum over count) for the window's requests."""


def read(ctx):
    n = ctx["counters"].get("queue_waits")
    if not n:
        return None
    return 1e3 * ctx["counters"]["queue_wait_s"] / n
