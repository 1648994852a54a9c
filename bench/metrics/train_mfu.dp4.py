"""Model FLOP/s of the trained tokens over the four chips' bf16 peak
(model step)."""
from bench import readers


def read(ctx):
    return readers.train_mfu(ctx)
