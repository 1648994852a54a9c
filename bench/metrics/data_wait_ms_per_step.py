"""The trainer's data_wait span per step (trainer)."""
from bench import readers


def read(ctx):
    return readers.data_wait_ms(ctx)
