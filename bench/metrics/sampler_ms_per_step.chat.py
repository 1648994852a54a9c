"""Device time of the fused top-k/top-p sampler per decode step in the
chat cell (kernels/sampling.py): the engine calls it as its own compiled
program, once per decode step and once per first token."""
from bench import readers

# the sampler's compiled program in the device trace
PROGRAM = "jit_sample_tokens"


def read(ctx):
    return readers.program_ms_per_step(ctx, PROGRAM)
