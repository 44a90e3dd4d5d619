"""The on-chip benchmark of the batch 2-D LP solver (``python bench/run.py``)."""
