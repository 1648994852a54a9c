"""Share of the four-chip training window in which no op runs on a
chip, averaged over the chips."""
from bench import readers


def read(ctx):
    return readers.device_idle_share(ctx)
