"""Host time per ``solver.solve`` span in the window: from the entry of
``Solver.solve`` until its asynchronous dispatch returns
(``bench.scopes``)."""
from bench.scopes import dispatch_ms as read  # noqa: F401
