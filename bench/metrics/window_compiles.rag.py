"""Compiles inside the RAG chat cell's window (device): there should be
none once set-up has warmed every shape."""
from bench import program_trace


def read(ctx):
    return program_trace.window_compiles(ctx)
