"""Reductions the per-layer metric files share.  Each file under
``bench/metrics/`` is the reader of one metric, found by the metric's
name, and most are one of these applied to the run's context
(``run_cell.ReadCtx``).  A reader that finds nothing to read returns
``None``, and the metric is left out of the line.
"""
from __future__ import annotations

from . import counters, trace_reduce

ENGINE_SPANS = ("prefill_chunk", "decode_step")


def _window_s(ctx) -> float:
    return ctx.window[1] - ctx.window[0]


def sched_host_ms(ctx):
    """Mean host time per ``engine.step()``: the harness's span around the
    step minus the time inside the engine's prefill-chunk and decode-step
    spans (whose device work ends inside them)."""
    steps = ctx.steps_in_window()
    if not steps:
        return None
    inner = sorted((s[0], s[1]) for s in ctx.spans if s[2] in ENGINE_SPANS)
    host = 0.0
    for st in steps:
        busy = sum(min(b, st.t1) - max(a, st.t0) for a, b in inner
                   if b > st.t0 and a < st.t1)
        host += (st.t1 - st.t0) - busy
    return {"value": 1e3 * host / len(steps)}


def data_wait_ms(ctx):
    """Mean duration of the trainer's ``data_wait`` span per step."""
    spans = ctx.spans_named("data_wait")
    if not spans:
        return None
    return {"value": 1e3 * sum(b - a for a, b, _, _ in spans) / len(spans)}


def serve_mfu(ctx):
    """Model operations of every prefill and decode token the window's
    steps ran, over the window times the chips' bf16 peak, in percent."""
    steps = ctx.steps_in_window()
    if not steps:
        return None
    ref, d = ctx.cell.reference, ctx.dims
    flops = 0.0
    for st in steps:
        flops += sum(counters.prefill_flops(ref, d, a, n)
                     for a, n in st.prefill)
        flops += sum(counters.decode_flops(ref, d, c) for c in st.decode_ctx)
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips
    return {"value": 100.0 * flops / (_window_s(ctx) * peak)}


def train_mfu(ctx):
    """Model operations (forward and backward, recomputation not counted)
    of the tokens of every step in the window, over the window times the
    chips' bf16 peak, in percent."""
    tr = ctx.run["train"]
    n = tr["steps"]
    if not n:
        return None
    seq = ctx.cell.mix["seq"]
    flops = n * tr["tokens_per_step"] * counters.train_flops_per_token(
        ctx.cell.reference, ctx.dims, seq)
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips
    return {"value": 100.0 * flops / (_window_s(ctx) * peak)}


def kernel_seconds(ctx, mark: str) -> float:
    """Device seconds, on chip 0, of ops whose name holds ``mark``."""
    t0, t1 = ctx.dev_window
    ops = trace_reduce.op_seconds(ctx.trace, t0, t1,
                                  match=lambda n: mark in n, device=0)
    return sum(ops.values())


def paged_attn_roofline(ctx, mark: str):
    """Least time of the paged decode kernel over the window's steps (the
    larger of its operations over the bf16 peak and its bytes over HBM
    bandwidth, from each step's live lengths), over the kernel's device
    time in the trace, in percent; ``bound`` says which term won."""
    steps = [s for s in ctx.steps_in_window() if s.decode_ctx]
    secs = kernel_seconds(ctx, mark)
    if not steps or secs <= 0:
        return None
    p = ctx.peaks
    least, mem_bound = 0.0, 0.0
    for st in steps:
        f, b = counters.paged_attn_cost(ctx.cell.reference, ctx.dims,
                                        st.decode_ctx)
        tf, tb = f / p["bf16_flops_per_s"], b / p["hbm_bytes_per_s"]
        least += max(tf, tb)
        mem_bound += tb >= tf
    bound = "memory" if mem_bound * 2 >= len(steps) else "compute"
    return {"value": 100.0 * least / secs, "bound": bound}


def program_ms_per_step(ctx, mark: str):
    """Device time per decode step of the compiled program (XLA module)
    whose name holds ``mark``, on chip 0, in ms."""
    steps = [s for s in ctx.steps_in_window() if s.decode_ctx]
    t0, t1 = ctx.dev_window
    secs = trace_reduce.module_seconds(ctx.trace, t0, t1, mark)
    if not steps or secs <= 0:
        return None
    return {"value": 1e3 * secs / len(steps)}


def device_idle_share(ctx):
    """1 - (union of device op intervals) / window, in percent, averaged
    over the chips."""
    t0, t1 = ctx.dev_window
    busy = trace_reduce.busy_s(ctx.trace, t0, t1)
    if busy <= 0:
        return None
    return {"value": 100.0 * (1.0 - busy / ((t1 - t0) / 1e9))}


def exposed_collective_ms(ctx):
    """Collective time during which no compute op runs on the same chip
    (``trace_reduce.exposed_collective_s``, averaged over the chips), per
    training step of the window, in ms; beside it the collectives' whole
    time per step and how many collective ops the window holds by name.
    A window with no collective op by name reads as nothing, so 0 is a
    reading and never a missed name."""
    n = ctx.run["train"]["steps"]
    t0, t1 = ctx.dev_window
    coll = trace_reduce.op_seconds(ctx.trace, t0, t1,
                                   match=trace_reduce.is_collective)
    if not n or not coll:
        return None
    chips = max(len(ctx.trace.devices), 1)
    exposed = trace_reduce.exposed_collective_s(ctx.trace, t0, t1)
    return {"value": 1e3 * exposed / n,
            "collective_ms_per_step": 1e3 * sum(coll.values()) / chips / n,
            "collective_ops": len(coll)}
