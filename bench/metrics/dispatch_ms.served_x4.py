"""Host time per ``serve.dispatch`` span in the traced window: each
flush's dispatch, from the call until the asynchronous dispatch
returns, backpressure excluded (``bench.chip_trace``)."""
from bench.chip_trace import dispatch_ms as read  # noqa: F401
