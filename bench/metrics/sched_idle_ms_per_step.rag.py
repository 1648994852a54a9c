"""Device idle time inside each engine step of the RAG chat cell, split
by the engine's phase open at the time (serving scheduler)."""
from bench import program_trace


def read(ctx):
    return program_trace.sched_idle_ms(ctx)
