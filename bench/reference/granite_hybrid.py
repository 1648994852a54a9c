"""Plain float32 reference of Granite-4.0-H (HF ``GraniteMoeHybrid``), one
pipeline stage of it, with this chip's share of the experts: forward,
loss and gradient in ``jax.numpy``, with no kernel, cache, batching or
sharding, every matmul at HIGHEST precision.  It imports nothing of the
program: its sizes come from the configuration file, its weights are the
parameter tree the harness made from the seed (read in the program's
layout), and it runs only after the program's state is freed.

The stage holds the first ``num_hidden_layers`` of the published
``layer_types`` (a period of ten: Mamba-2 mixers, attention at position
5).  With ``e`` = embedding_multiplier, ``r`` = residual_multiplier, ``s``
= logits_scaling and ``n`` an RMSNorm:

    x0 = embed[t] * e
    h  = x + r * mixer(n1(x))
    x' = h + r * (moe(n2(h)) + shared(n2(h)))
    logits = n_f(x_L) @ embed.T / s

    mixer, Mamba-2:  z, xBC, dt = n1(x) Win;  xBC = silu(conv4(xBC) + b);
                     x_h, B, C = xBC;  dt = softplus(dt + dt_bias);
                     S_t = S_{t-1} exp(dt_t A) + dt_t x_t B_t^T,
                     A = -exp(A_log);
                     y_t = S_t C_t + D x_t;   out = (w * n(y * silu(z))) Wout
    mixer, attention: causal GQA with no positional encoding, scores
                     scaled by attention_multiplier (not hd^-0.5)
    moe:             f32 router logits over all experts, top-k, gates a
                     softmax over the k chosen logits; each held expert's
                     silu(z Wg) (z Wu) Wd weighted by its gate
    shared:          silu(z Wg_s) (z Wu_s) Wd_s, for every token

The SSM runs token by token as the recurrence above (the program runs
the chunked SSD form); each layer's weights are cast to f32 only inside
its own step, after the previous layer has finished.  Departures from
HF, each what the configuration states:

* Only the held experts ``[expert_offset, expert_offset +
  num_local_experts)`` contribute: the router keeps its published width
  and top-k, and what the absent experts would add is left out, here as
  in the program (one chip of an expert-parallel deployment).
* RMSNorm weights, the gated norm's included, are stored as offsets from
  one: ``n(x) = x / rms(x) * (1 + w)`` (the same function as HF's ``w``).
* The stage holds the embedding and the tied head, which a real first
  and last stage would split.

``quant="fp8"`` is the control: the same computation with both operands
of every matmul (the router's included) rounded to float8 e4m3 with a
per-tensor scale, the next precision below the bfloat16 served.

A configuration file names this module (``"reference":
"granite_hybrid"``); besides the forward, loss and gradient it gives the
weight rule (``leaf_rule``), the file keys the program takes beyond the
common ones (``PROGRAM_KEYS``), the per-token counts the counters compose
(``matmul_flops_per_token``, ``attn_layers``, ``kv_bytes_per_token``) and
the byte counts the cell's readers use (``weight_bytes``,
``state_bytes_per_lane``).  The counts are this chip's share: a token's
routed experts count as top_k x num_local_experts / num_routed_experts
experts (the expectation; the exact rows come from the program's
``expert_rows`` count).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.common import BenchError

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0           # largest finite float8_e4m3fn
Q_BLOCK = 1024           # query rows per attention block

# the file's model keys -> the program's ArchConfig fields, beyond the
# keys every architecture has
PROGRAM_KEYS = {
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "shared_intermediate_size": "d_ff_shared",
    "num_routed_experts": "n_experts",
    "num_local_experts": "experts_held",
    "expert_offset": "expert_offset",
    "num_experts_per_tok": "top_k",
    "mamba_d_state": "ssm_state",
    "mamba_n_heads": "ssm_heads",
    "mamba_expand": "ssm_expand",
    "mamba_chunk_size": "ssm_chunk",
    "mamba_d_conv": "conv_width",
    "mamba_gated_norm": "ssm_gated_norm",
    "position_embedding_type": "position_embedding",
    "attention_multiplier": "attention_multiplier",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "logits_scaling": "logits_scaling",
}

PERIOD = 10              # the pattern: one period of layer_types


def dims_of(conf: dict) -> dict:
    """The sizes the reference needs, from a configuration file: the
    ``model`` block, and the stage's layer kinds, the first
    ``num_hidden_layers`` of the published ``layer_types``."""
    m = conf["model"]
    L, D = m["num_hidden_layers"], m["hidden_size"]
    Hs, di = m["mamba_n_heads"], m["mamba_expand"] * m["hidden_size"]
    if di % Hs or di // Hs != m["mamba_d_head"]:
        raise BenchError(f"{conf['name']}: mamba_d_head {m['mamba_d_head']}"
                         f" is not d_inner {di} / mamba_n_heads {Hs}")
    kinds = list(conf["layer_types"][:L])
    if len(kinds) != L or L % PERIOD:
        raise BenchError(f"{conf['name']}: {L} layers are not whole "
                         f"periods of layer_types")
    return {"L": L, "D": D, "H": m["num_attention_heads"],
            "K": m["num_key_value_heads"], "hd": m["head_dim"],
            "F": m["intermediate_size"], "Fs": m["shared_intermediate_size"],
            "V": m["vocab_size"], "E": m["num_routed_experts"],
            "Eh": m["num_local_experts"], "lo": m["expert_offset"],
            "k": m["num_experts_per_tok"], "N": m["mamba_d_state"],
            "Hs": Hs, "P": m["mamba_d_head"], "di": di,
            "W": m["mamba_d_conv"], "eps": float(m["norm_eps"]),
            "emb": float(m["embedding_multiplier"]),
            "res": float(m["residual_multiplier"]),
            "logit_div": float(m["logits_scaling"]),
            "attn_scale": float(m["attention_multiplier"]),
            "kinds": kinds, "tied": bool(m["tie_word_embeddings"]),
            "act_bytes": 2 if m["dtype"] == "bfloat16" else 4}


def leaf_rule(path: tuple, shape: tuple, d: dict) -> tuple[float, float]:
    """(mean, standard deviation) of a parameter leaf's random values, by
    its whole path.  Matrices use 1/sqrt(fan-in), the embedding so that
    x0 has unit norm; norm offsets and the conv bias are small but
    nonzero, so their paths are exercised; the SSM's step bias, decay and
    skip are drawn about working values (a step near 0.02, decay rates
    1-16, skip near one)."""
    D = d["D"]
    # the tied embedding: x0 = embed[t] * e of unit norm.  A wider draw
    # makes x0 outweigh what the layers add, so the head reads the input
    # token back and greedy decoding repeats it
    top = {("embed",): (0.0, 1.0 / (d["emb"] * D ** 0.5)),
           ("final_norm",): (0.0, 0.1)}
    block = {("ln1",): (0.0, 0.1), ("ln2",): (0.0, 0.1),
             ("attn", "wq"): (0.0, D ** -0.5),
             ("attn", "wk"): (0.0, D ** -0.5),
             ("attn", "wv"): (0.0, D ** -0.5),
             ("attn", "wo"): (0.0, (d["H"] * d["hd"]) ** -0.5),
             ("ssm", "in_proj"): (0.0, D ** -0.5),
             ("ssm", "conv_w"): (0.0, 0.5), ("ssm", "conv_b"): (0.0, 0.1),
             ("ssm", "dt_bias"): (-4.0, 0.5),
             ("ssm", "A_log"): (math.log(4.0), 0.5),
             ("ssm", "D"): (1.0, 0.1), ("ssm", "norm"): (0.0, 0.1),
             ("ssm", "out_proj"): (0.0, d["di"] ** -0.5),
             ("moe", "router"): (0.0, D ** -0.5),
             ("moe", "wg"): (0.0, D ** -0.5), ("moe", "wu"): (0.0, D ** -0.5),
             ("moe", "wd"): (0.0, d["F"] ** -0.5),
             ("moe", "shared_wg"): (0.0, D ** -0.5),
             ("moe", "shared_wu"): (0.0, D ** -0.5),
             ("moe", "shared_wd"): (0.0, d["Fs"] ** -0.5)}
    if path in top:
        return top[path]
    if len(path) > 2 and path[0] == "blocks" and path[2:] in block:
        return block[path[2:]]
    raise BenchError(f"no weight rule for parameter leaf {'/'.join(path)}")


# ---------------------------------------------------------------------------
# per-token counts and bytes (bench/counters.py and the readers)


def attn_layers(d: dict) -> int:
    """Layers that attend over the KV cache: the attention layers."""
    return sum(k == "attention" for k in d["kinds"])


def kv_bytes_per_token(d: dict, itemsize: int) -> int:
    """Bytes of K and V one token keeps over the attention layers."""
    return 2 * attn_layers(d) * d["K"] * d["hd"] * itemsize


def _mixer_macs(d: dict, kind: str) -> int:
    D = d["D"]
    if kind == "attention":
        return 2 * D * d["H"] * d["hd"] + 2 * D * d["K"] * d["hd"]
    return D * (2 * d["di"] + 2 * d["N"] + d["Hs"]) + d["di"] * D


def matmul_flops_per_token(d: dict) -> float:
    """Forward matmul operations of one token through the stage and the
    head, this chip's share: each layer's mixer projections, the router,
    top_k x Eh / E routed experts (the expectation), the shared expert,
    and the LM head."""
    D = d["D"]
    moe = (D * d["E"] + d["k"] * d["Eh"] / d["E"] * 3 * D * d["F"]
           + 3 * D * d["Fs"])
    return 2.0 * (sum(_mixer_macs(d, k) + moe for k in d["kinds"])
                  + D * d["V"])


def state_bytes_per_lane(d: dict) -> int:
    """One lane's recurrent state over the Mamba layers: the conv history
    (W-1 x (d_inner + 2N), activation dtype) and the SSM state (heads x
    P x N, f32)."""
    n = sum(k != "attention" for k in d["kinds"])
    conv = (d["W"] - 1) * (d["di"] + 2 * d["N"]) * d["act_bytes"]
    return n * (conv + d["Hs"] * d["P"] * d["N"] * 4)


def weight_bytes(d: dict) -> int:
    """Bytes of the stage's weights as served: matrices and norms in the
    activation dtype, the router and the SSM's step bias, decay and skip
    in f32."""
    D = d["D"]
    ch = d["di"] + 2 * d["N"]
    act = d["V"] * D + D                                # embed, final norm
    f32 = 0
    for kind in d["kinds"]:
        act += (2 * D + _mixer_macs(d, kind)            # norms, projections
                + 3 * D * d["Fs"] + 3 * d["Eh"] * D * d["F"])
        f32 += D * d["E"]                               # router
        if kind != "attention":
            act += d["W"] * ch + ch + d["di"]           # conv, its bias, norm
            f32 += 3 * d["Hs"]
    return int(act * d["act_bytes"] + f32 * 4)


# ---------------------------------------------------------------------------
# the forward pass


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(eq, a, b, quant=None):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rmsnorm(x, w, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * (1.0 + w.astype(jnp.float32))


def silu(x):
    return x * jax.nn.sigmoid(x)


def attention(h, a, d, quant=None):
    """Causal GQA with no positions, scores x attention_multiplier.
    h: (S, D) -> (S, D)."""
    S = h.shape[0]
    H, K, hd = d["H"], d["K"], d["hd"]
    G = H // K
    q = mm("sd,dhk->shk", h, a["wq"], quant)
    k = mm("sd,dhk->shk", h, a["wk"], quant)
    v = mm("sd,dhk->shk", h, a["wv"], quant)
    out = []
    for lo in range(0, S, Q_BLOCK):
        hi = min(S, lo + Q_BLOCK)
        qb = q[lo:hi].reshape(hi - lo, K, G, hd)
        s = mm("qkgh,skh->kgqs", qb, k, quant) * d["attn_scale"]
        mask = jnp.arange(S)[None, :] <= jnp.arange(lo, hi)[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out.append(mm("kgqs,skh->qkgh", p, v, quant).reshape(hi - lo, H, hd))
    return mm("shk,hkd->sd", jnp.concatenate(out, 0), a["wo"], quant)


def mamba(h, m, d, quant=None):
    """The Mamba-2 mixer, its SSM as a token-by-token recurrence.
    h: (S, D) -> (S, D)."""
    S = h.shape[0]
    di, N, Hs, P, W = d["di"], d["N"], d["Hs"], d["P"], d["W"]
    zxbcdt = mm("sd,de->se", h, m["in_proj"], quant)
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * N], \
        zxbcdt[:, 2 * di + 2 * N:]
    w = m["conv_w"].astype(jnp.float32)
    xpad = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1])), xbc])
    conv = sum(xpad[i:i + S] * w[i] for i in range(W))
    xbc = silu(conv + m["conv_b"].astype(jnp.float32))
    xh = xbc[:, :di].reshape(S, Hs, P)
    Bm, Cm = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + m["dt_bias"].astype(jnp.float32))   # (S, Hs)
    A = -jnp.exp(m["A_log"].astype(jnp.float32))

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (state * jnp.exp(dt_t * A)[:, None, None]
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, jnp.einsum("hpn,n->hp", state, c_t, precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((Hs, P, N), jnp.float32),
                        (xh, Bm, Cm, dt))
    y = y + m["D"].astype(jnp.float32)[None, :, None] * xh
    y = rmsnorm(y.reshape(S, di) * silu(z), m["norm"], d["eps"])
    return mm("se,ed->sd", y, m["out_proj"], quant)


def experts(h, m, d, quant=None):
    """The held experts' part of the routed layer plus the shared expert.
    h: (S, D) -> (S, D)."""
    logits = mm("sd,de->se", h, m["router"], quant)
    top, idx = jax.lax.top_k(logits, d["k"])
    gates = jax.nn.softmax(top, -1)
    out = jnp.zeros(h.shape, jnp.float32)
    for e in range(d["Eh"]):
        g = jnp.sum(jnp.where(idx == d["lo"] + e, gates, 0.0), -1)   # (S,)
        z = (silu(mm("sd,df->sf", h, m["wg"][e], quant))
             * mm("sd,df->sf", h, m["wu"][e], quant))
        out = out + g[:, None] * mm("sf,fd->sd", z, m["wd"][e], quant)
    zs = (silu(mm("sd,df->sf", h, m["shared_wg"], quant))
          * mm("sd,df->sf", h, m["shared_wu"], quant))
    return out + mm("sf,fd->sd", zs, m["shared_wd"], quant)


def layer_params(params, i: int) -> dict:
    """Layer ``i``'s weights out of the program's stacked pattern."""
    blk = params["blocks"][f"p{i % PERIOD}"]
    return jax.tree.map(lambda a: a[i // PERIOD], blk)


def hidden(params, tokens, d, quant=None, remat=False):
    """Final-normed hidden states (S, D) of one sequence.  Each layer
    starts after the previous one has finished (an optimization
    barrier), so only one layer's weights are upcast at a time."""
    x = params["embed"][tokens].astype(jnp.float32) * d["emb"]

    def block(x, p, kind):
        h = rmsnorm(x, p["ln1"], d["eps"])
        mix = (attention(h, p["attn"], d, quant) if kind == "attention"
               else mamba(h, p["ssm"], d, quant))
        x = x + d["res"] * mix
        h = rmsnorm(x, p["ln2"], d["eps"])
        return x + d["res"] * experts(h, p["moe"], d, quant)

    for i, kind in enumerate(d["kinds"]):
        f = jax.checkpoint(block, static_argnums=2) if remat else block
        x, p = jax.lax.optimization_barrier((x, layer_params(params, i)))
        x = f(x, p, kind)
    return rmsnorm(x, params["final_norm"], d["eps"])


def logits_at(params, tokens, rows, d, quant=None):
    """Logits (len(rows), V) at positions ``rows`` of one sequence."""
    h = hidden(params, tokens, d, quant)[rows]
    return mm("sd,vd->sv", h, params["embed"], quant) / d["logit_div"]


def loss_and_grad(p32, tokens, d, quant=None):
    """Mean next-token cross entropy over a (B, S) batch and its gradient
    with respect to the f32 parameters ``p32``, one row at a time."""
    B, S = tokens.shape

    def row_loss(p, t):
        h = hidden(p, t, d, quant, remat=True)[:-1]
        lg = mm("sd,vd->sv", h, p["embed"], quant) / d["logit_div"]
        lp = jax.nn.log_softmax(lg, -1)
        return -jnp.sum(jnp.take_along_axis(lp, t[1:, None], -1))

    vg = jax.value_and_grad(row_loss)

    def body(carry, row):
        tot, acc = carry
        l, g = vg(p32, row)
        return (tot + l, jax.tree.map(jnp.add, acc, g)), None

    zeros = jax.tree.map(jnp.zeros_like, p32)
    (tot, grads), _ = jax.lax.scan(body, (0.0, zeros), tokens)
    n = B * (S - 1)
    return tot / n, jax.tree.map(lambda g: g / n, grads)
