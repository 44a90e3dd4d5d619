"""Drivers: one module per kind of deployment, named by a configuration's
``driver``.  Each has ``run(cell, ctx) -> Outcome``."""
