"""Share of the traced window in which no operation ran on the device
(1 - busy / window), averaged over the chips the cell holds."""
from bench.trace_reduce import read_idle as read  # noqa: F401
