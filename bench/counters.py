"""Operations and bytes the algorithms need, computed from shapes.  The
sizes come from the reference's ``dims`` (the configuration file), never
from the program.  A multiply-add counts as two operations; operations a
program recomputes (rematerialization) are not counted.
"""
from __future__ import annotations


def matmul_flops_per_token(d: dict) -> float:
    """Forward matmul operations of one token through the whole model:
    the q/k/v/o projections, the MLP and the LM head."""
    D, H, K, hd, F = d["D"], d["H"], d["K"], d["hd"], d["F"]
    attn = D * H * hd + 2 * D * K * hd + H * hd * D
    mlp = (3 if d["mlp"] == "swiglu" else 2) * D * F
    return 2.0 * (d["L"] * (attn + mlp) + D * d["V"])


def attn_flops(d: dict, ctx: float) -> float:
    """Forward attention operations of one query token over ``ctx`` keys
    (scores and the value combine), over every layer."""
    return 4.0 * d["L"] * d["H"] * d["hd"] * ctx


def prefill_flops(d: dict, start: int, n: int) -> float:
    """Prompt tokens at positions [start, start + n) under causal
    attention: token p attends over p + 1 keys."""
    ctx_sum = n * start + n * (n + 1) / 2
    return n * matmul_flops_per_token(d) + attn_flops(d, ctx_sum)


def decode_flops(d: dict, ctx: int) -> float:
    """One decode token attending over ``ctx`` keys (itself included)."""
    return matmul_flops_per_token(d) + attn_flops(d, ctx)


def train_flops_per_token(d: dict, seq: int) -> float:
    """Forward and backward (three forwards) per trained token at
    sequence length ``seq``, causal: a token attends over (seq + 1) / 2
    keys on average."""
    return 3.0 * (matmul_flops_per_token(d) + attn_flops(d, (seq + 1) / 2))


def paged_attn_cost(d: dict, ctxs, kv_itemsize: int = 2,
                    q_itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) the paged decode kernel needs for one step whose
    decode lanes attend over ``ctxs`` live tokens: every live K and V row
    read once per layer, the query read and the output written once."""
    L, H, K, hd = d["L"], d["H"], d["K"], d["hd"]
    live = float(sum(ctxs))
    flops = 4.0 * L * H * hd * live
    kv = 2.0 * L * K * hd * kv_itemsize * live
    qo = 2.0 * L * len(ctxs) * H * hd * q_itemsize
    return flops, kv + qo
