"""Host time per engine.step() in the chat cell (serving scheduler)."""
from bench import readers


def read(ctx):
    return readers.sched_host_ms(ctx)
