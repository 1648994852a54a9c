"""The grouped expert matmul's share of its roofline in the RAG chat cell
(kernels/expert_gmm.py): the kernel's least time from each engine step's
``expert_rows``, over the kernel's device time in the trace."""
from bench import program_trace, readers

# the kernel's name in the device trace
KERNEL = "expert_gmm"


def cost(d: dict, rows: int, calls: int) -> tuple[float, float]:
    """(operations, bytes) of ``calls`` kernel calls that together take
    ``rows`` rows: 2 x 3 x D x F operations a row (gate, up and down
    projections); each call reads the held experts' three matrices once,
    and each row is read in and written out once."""
    D, F, item = d["D"], d["F"], d["act_bytes"]
    flops = 6.0 * D * F * rows
    nbytes = calls * d["Eh"] * 3 * D * F * item + rows * 2 * D * item
    return flops, nbytes


def read(ctx):
    steps = [a for a in program_trace.step_counts(ctx) if "expert_rows" in a]
    secs = readers.kernel_seconds(ctx, KERNEL)
    if not steps or secs <= 0:
        return None
    d, p = ctx.dims, ctx.peaks
    chunk = {**ctx.cell.config["engine"],
             **ctx.cell.mix.get("engine", {})}["prefill_chunk"]
    least, mem_bound, n = 0.0, 0, 0
    for a in steps:
        # one decode program and one program per prefill chunk, each
        # calling the kernel once in every layer
        programs = (a["lanes"] > 0) + -(-a["prefill_tokens"] // chunk)
        if not programs:
            continue
        f, b = cost(d, a["expert_rows"], programs * d["L"])
        tf, tb = f / p["bf16_flops_per_s"], b / p["hbm_bytes_per_s"]
        least += max(tf, tb)
        mem_bound += tb >= tf
        n += 1
    if not n:
        return None
    return {"value": 100.0 * least / secs,
            "bound": "memory" if mem_bound * 2 >= n else "compute"}
