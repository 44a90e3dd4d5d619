"""Share of the HBM roofline the LP kernel reached in the window: the
bytes its work needs (``bench.roofline``) at the peak bandwidth, over
the kernel's device time in the trace."""
from bench.roofline import read  # noqa: F401
