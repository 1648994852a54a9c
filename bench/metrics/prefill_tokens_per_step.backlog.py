"""Prompt tokens prefilled per engine step in the code-backlog cell, as
the engine counts them (serving scheduler)."""
from bench import program_trace


def read(ctx):
    return program_trace.mean_count(ctx, "prefill_tokens")
