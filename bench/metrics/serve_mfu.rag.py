"""Model FLOP/s of the RAG chat window's prefill and decode tokens over
the bf16 peak (model step), with this chip's share of the experts."""
from bench import readers


def read(ctx):
    return readers.serve_mfu(ctx)
