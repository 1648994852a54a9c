"""Gradient exchange time not hidden behind compute, per step of the
four-chip data-parallel cell (dist/bucketing.py)."""
from bench import readers


def read(ctx):
    return readers.exposed_collective_ms(ctx)
