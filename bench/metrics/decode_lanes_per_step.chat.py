"""Lanes decoded per decoding engine step in the chat cell, as the
engine counts them (serving scheduler).  At a fixed offered rate a
faster engine holds fewer requests at once (Little's law)."""
from bench import program_trace


def read(ctx):
    return program_trace.mean_count(ctx, "lanes", decoding_only=True)
