"""The decode step's share of its roofline in the RAG chat cell (model
step): its least time, every weight read once, each decoding lane's SSM
and conv state read and written once and the live KV read once, over HBM
bandwidth, against the decode program's device time per step.  A step
that copies the states rather than updating them in place reads far
lower."""
from bench import readers

# the decode step's compiled program in the device trace
PROGRAM = "jit_decode_paged"


def least_bytes(ref, d: dict, ctxs) -> float:
    """Bytes a decode step must move: the weights, each of the lanes'
    states both ways, and the KV rows of their live contexts."""
    kv = ref.kv_bytes_per_token(d, d["act_bytes"])
    return (ref.weight_bytes(d) + 2 * ref.state_bytes_per_lane(d) * len(ctxs)
            + kv * sum(ctxs))


def read(ctx):
    steps = [s for s in ctx.steps_in_window() if s.decode_ctx]
    per_step = readers.program_ms_per_step(ctx, PROGRAM)
    if not steps or per_step is None:
        return None
    ref, d = ctx.cell.reference, ctx.dims
    least_ms = 1e3 * sum(least_bytes(ref, d, s.decode_ctx) for s in steps) / (
        ctx.peaks["hbm_bytes_per_s"] * len(steps))
    return {"value": 100.0 * least_ms / per_step["value"],
            "least_ms": least_ms, "program_ms": per_step["value"]}
