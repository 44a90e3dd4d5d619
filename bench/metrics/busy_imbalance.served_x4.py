"""The busiest chip's busy time in the traced window over the mean of
the chips the cell holds; a chip's busy time is the union of its ``XLA
Ops`` intervals inside the window (``bench.chip_trace``)."""
from bench.chip_trace import busy_imbalance as read  # noqa: F401
