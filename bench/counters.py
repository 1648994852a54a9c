"""Operations and bytes the algorithms need, computed from shapes.  The
sizes come from the reference's ``dims`` (the configuration file), never
from the program; the per-token counts that differ between architectures
(matmul operations, attention layers, KV bytes) come from the cell's
reference module ``ref``, and ``d["H"]`` and ``d["hd"]`` are its attention
layers' query heads and head size.  A multiply-add counts as two
operations; operations a program recomputes (rematerialization) are not
counted.
"""
from __future__ import annotations


def attn_flops(ref, d: dict, ctx: float) -> float:
    """Forward attention operations of one query token over ``ctx`` keys
    (scores and the value combine), over every attention layer."""
    return 4.0 * ref.attn_layers(d) * d["H"] * d["hd"] * ctx


def prefill_flops(ref, d: dict, start: int, n: int) -> float:
    """Prompt tokens at positions [start, start + n) under causal
    attention: token p attends over p + 1 keys."""
    ctx_sum = n * start + n * (n + 1) / 2
    return n * ref.matmul_flops_per_token(d) + attn_flops(ref, d, ctx_sum)


def decode_flops(ref, d: dict, ctx: int) -> float:
    """One decode token attending over ``ctx`` keys (itself included)."""
    return ref.matmul_flops_per_token(d) + attn_flops(ref, d, ctx)


def train_flops_per_token(ref, d: dict, seq: int) -> float:
    """Forward and backward (three forwards) per trained token at
    sequence length ``seq``, causal: a token attends over (seq + 1) / 2
    keys on average."""
    return 3.0 * (ref.matmul_flops_per_token(d)
                  + attn_flops(ref, d, (seq + 1) / 2))


def paged_attn_cost(ref, d: dict, ctxs, kv_itemsize: int = 2,
                    q_itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) the paged decode kernel needs for one step whose
    decode lanes attend over ``ctxs`` live tokens: every live K and V row
    of every attention layer read once, the query read and the output
    written once per attention layer."""
    L, H, hd = ref.attn_layers(d), d["H"], d["hd"]
    live = float(sum(ctxs))
    flops = 4.0 * L * H * hd * live
    kv = ref.kv_bytes_per_token(d, kv_itemsize) * live
    qo = 2.0 * L * len(ctxs) * H * hd * q_itemsize
    return flops, kv + qo
