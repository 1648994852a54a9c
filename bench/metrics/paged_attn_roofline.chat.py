"""The paged decode kernel's share of its roofline in the chat cell
(kernels/paged_attention.py)."""
from bench import readers

# the kernel's name in the device trace
KERNEL = "paged_attention"


def read(ctx):
    return readers.paged_attn_roofline(ctx, KERNEL)
