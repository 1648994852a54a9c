"""Share of the RAG chat window in which no op runs on the device."""
from bench import readers


def read(ctx):
    return readers.device_idle_share(ctx)
