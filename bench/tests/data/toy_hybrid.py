"""A toy reference module for a hybrid architecture the benchmark has no
cell for: the repo's Jamba pattern (Mamba-2 mixers, one attention layer
in eight, experts on every other layer) at a tiny size.  It exists to
show that a new architecture brings its reference, weight rule, program
keys and per-token counts as files of its own, with no harness file
edited.  It gives weights, keys and counts only: its forward and loss are
not written."""
from __future__ import annotations

import math

from bench.common import BenchError

PROGRAM_KEYS = {
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "num_local_experts": "n_experts",
    "num_experts_per_tok": "top_k",
    "mamba_d_state": "ssm_state",
    "mamba_n_heads": "ssm_heads",
    "mamba_expand": "ssm_expand",
}

PERIOD = 8          # one attention layer (position 4) in every eight


def dims_of(conf: dict) -> dict:
    m = conf["model"]
    return {"L": m["num_hidden_layers"], "D": m["hidden_size"],
            "H": m["num_attention_heads"], "K": m["num_key_value_heads"],
            "hd": m["head_dim"], "F": m["intermediate_size"],
            "V": m["vocab_size"], "E": m["num_local_experts"],
            "top_k": m["num_experts_per_tok"], "N": m["mamba_d_state"],
            "Hs": m["mamba_n_heads"],
            "inner": m["mamba_expand"] * m["hidden_size"]}


def leaf_rule(path: tuple, shape: tuple, d: dict) -> tuple[float, float]:
    """(mean, std) by whole path: an expert's ``wd`` and a dense MLP's
    ``wd`` are different leaves, and the SSM's decay and skip leaves are
    drawn about their working values."""
    D, F, inner = d["D"], d["F"], d["inner"]
    top = {("embed",): (0.0, 0.02), ("lm_head",): (0.0, D ** -0.5),
           ("final_norm",): (0.0, 0.1)}
    block = {("ln1",): (0.0, 0.1), ("ln2",): (0.0, 0.1),
             ("attn", "wq"): (0.0, D ** -0.5), ("attn", "wk"): (0.0, D ** -0.5),
             ("attn", "wv"): (0.0, D ** -0.5),
             ("attn", "wo"): (0.0, (d["H"] * d["hd"]) ** -0.5),
             ("mlp", "wg"): (0.0, D ** -0.5), ("mlp", "wu"): (0.0, D ** -0.5),
             ("mlp", "wd"): (0.0, F ** -0.5),
             ("moe", "router"): (0.0, D ** -0.5),
             ("moe", "wg"): (0.0, D ** -0.5), ("moe", "wu"): (0.0, D ** -0.5),
             ("moe", "wd"): (0.0, 0.5 * F ** -0.5),
             ("ssm", "in_proj"): (0.0, D ** -0.5),
             ("ssm", "out_proj"): (0.0, inner ** -0.5),
             ("ssm", "conv_w"): (0.0, 0.5), ("ssm", "conv_b"): (0.0, 0.1),
             ("ssm", "A_log"): (math.log(4.0), 0.5),
             ("ssm", "D"): (1.0, 0.1), ("ssm", "dt_bias"): (-4.0, 0.5)}
    if path in top:
        return top[path]
    if len(path) > 2 and path[0] == "blocks" and path[2:] in block:
        return block[path[2:]]
    raise BenchError(f"no weight rule for parameter leaf {'/'.join(path)}")


def attn_layers(d: dict) -> int:
    return d["L"] // PERIOD


def kv_bytes_per_token(d: dict, itemsize: int) -> int:
    return 2 * attn_layers(d) * d["K"] * d["hd"] * itemsize


def matmul_flops_per_token(d: dict) -> float:
    D, F, inner, N, Hs = d["D"], d["F"], d["inner"], d["N"], d["Hs"]
    n_attn = attn_layers(d)
    n_ssm = d["L"] - n_attn
    n_moe = d["L"] // 2
    attn = D * d["H"] * d["hd"] * 2 + 2 * D * d["K"] * d["hd"]
    ssm = D * (2 * inner + 2 * N + Hs) + inner * D
    dense = 3 * D * F
    moe = D * d["E"] + d["top_k"] * 3 * D * F
    return 2.0 * (n_attn * attn + n_ssm * ssm + (d["L"] - n_moe) * dense
                  + n_moe * moe + D * d["V"])


def logits_at(params, tokens, rows, d, quant=None):
    raise NotImplementedError("a toy: weights, keys and counts only")


def loss_and_grad(p32, tokens, d, quant=None):
    raise NotImplementedError("a toy: weights, keys and counts only")
