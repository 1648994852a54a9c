"""Model FLOP/s of the backlog window's prefill and decode tokens over the bf16 peak (model step)."""
from bench import readers


def read(ctx):
    return readers.serve_mfu(ctx)
