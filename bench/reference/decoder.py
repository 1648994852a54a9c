"""Plain float32 reference of the dense decoder the benchmark's
configurations run: forward, loss and gradient in ``jax.numpy``, with no
kernel, cache, batching or sharding, and every matmul at HIGHEST
precision.  It imports nothing of the program: its sizes come from the
configuration file, its weights are the parameter tree the harness made
from the seed (read in the program's layout), and it is run only after
the program's state is freed.

The layer is the published pre-norm block of Qwen1.5 and StarCoder2 as
the configuration files state it:

    h = x + Wo . attn(rope(Wq n1(x) + bq), rope(Wk n1(x) + bk), Wv n1(x) + bv)
    y = h + mlp(n2(h))        mlp = Wd (silu(Wg z) * Wu z)   (swiglu)
                              mlp = Wd gelu_tanh(Wu z)         (gelu)

with causal grouped-query attention and rotate-half RoPE.  Departures of
the program from the published models, kept here so that the reference
computes what the configuration states:

* RMSNorm weights are stored as offsets from one: ``n(x) = x /
  rms(x) * (1 + w)``.  Qwen1.5 stores ``w`` itself (the same function).
* StarCoder2 publishes LayerNorm and a bias on every linear layer; the
  program runs RMSNorm and biases on q, k and v only (none on the output
  projection or the MLP), and so does this file.
* StarCoder2's 4096-token sliding window is not applied: every cell keeps
  prompt plus output at or under 4096 tokens, where full causal
  attention is the same function.

``quant="fp8"`` is the control: the same computation with both operands
of every matmul rounded to float8 e4m3 (per-tensor scale), the next
precision below the bfloat16 the configurations serve in.

A configuration file names this module (``"reference": "decoder"``), and
the harness reaches it only through the cell: besides the forward, loss
and gradient it gives the weight rule (``leaf_rule``), the file keys the
program takes beyond the common ones (``PROGRAM_KEYS``) and the per-token
counts the counters compose (``matmul_flops_per_token``, ``attn_layers``,
``kv_bytes_per_token``).
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
    "intermediate_size": "d_ff",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "qkv_bias": "qkv_bias",
}


def dims_of(conf: dict) -> dict:
    """The sizes the reference needs, from a configuration file."""
    m = conf["model"]
    return {"L": m["num_hidden_layers"], "D": m["hidden_size"],
            "H": m["num_attention_heads"], "K": m["num_key_value_heads"],
            "hd": m["head_dim"], "F": m["intermediate_size"],
            "V": m["vocab_size"], "theta": float(m["rope_theta"]),
            "eps": float(m["norm_eps"]), "mlp": m["mlp"],
            "tied": bool(m["tie_word_embeddings"])}


def leaf_rule(path: tuple, shape: tuple, d: dict) -> tuple[float, float]:
    """(mean, standard deviation) of a parameter leaf's random values, by
    its whole path.  Matrices use 1/sqrt(fan-in), so activations keep unit
    scale through the stack; norm offsets and biases are small but
    nonzero, so their paths are exercised and checked."""
    D = d["D"]
    top = {("embed",): 0.02, ("lm_head",): D ** -0.5, ("final_norm",): 0.1}
    block = {("attn", "wq"): D ** -0.5, ("attn", "wk"): D ** -0.5,
             ("attn", "wv"): D ** -0.5,
             ("attn", "wo"): (d["H"] * d["hd"]) ** -0.5,
             ("mlp", "wg"): D ** -0.5, ("mlp", "wu"): D ** -0.5,
             ("mlp", "wd"): d["F"] ** -0.5,
             ("attn", "bq"): 0.1, ("attn", "bk"): 0.1, ("attn", "bv"): 0.1,
             ("ln1",): 0.1, ("ln2",): 0.1}
    if path in top:
        return 0.0, top[path]
    if len(path) > 2 and path[0] == "blocks" and path[2:] in block:
        return 0.0, block[path[2:]]
    raise BenchError(f"no weight rule for parameter leaf {'/'.join(path)}")


# ---------------------------------------------------------------------------
# per-token counts (bench/counters.py composes them)


def matmul_flops_per_token(d: dict) -> float:
    """Forward matmul operations of one token through the whole model:
    the q/k/v/o projections, the MLP and the LM head."""
    D, H, K, hd, F = d["D"], d["H"], d["K"], d["hd"], d["F"]
    attn = D * H * hd + 2 * D * K * hd + H * hd * D
    mlp = (3 if d["mlp"] == "swiglu" else 2) * D * F
    return 2.0 * (d["L"] * (attn + mlp) + D * d["V"])


def attn_layers(d: dict) -> int:
    """Layers that attend over the KV cache: every layer."""
    return d["L"]


def kv_bytes_per_token(d: dict, itemsize: int) -> int:
    """Bytes of K and V one token keeps over every attention layer."""
    return 2 * attn_layers(d) * d["K"] * d["hd"] * itemsize


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


def rope(x, pos, theta):
    """x: (S, heads, hd); rotate-half with inv freq theta^(-i/half)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(q, k, v, quant=None):
    """Causal GQA.  q: (S, H, hd); k, v: (S, K, hd) -> (S, H, hd)."""
    S, H, hd = q.shape
    K = k.shape[1]
    G = H // K
    out = []
    for lo in range(0, S, Q_BLOCK):
        hi = min(S, lo + Q_BLOCK)
        qb = q[lo:hi].reshape(hi - lo, K, G, hd)
        s = mm("qkgh,skh->kgqs", qb, k, quant) / math.sqrt(hd)
        mask = jnp.arange(S)[None, :] <= jnp.arange(lo, hi)[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = mm("kgqs,skh->qkgh", p, v, quant)
        out.append(o.reshape(hi - lo, H, hd))
    return jnp.concatenate(out, 0)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def layer(x, p, pos, d, quant=None):
    """One decoder block on one sequence.  x: (S, D) f32."""
    a = p["attn"]
    h = rmsnorm(x, p["ln1"], d["eps"])
    q = mm("sd,dhk->shk", h, a["wq"], quant) + a["bq"].astype(jnp.float32)
    k = mm("sd,dhk->shk", h, a["wk"], quant) + a["bk"].astype(jnp.float32)
    v = mm("sd,dhk->shk", h, a["wv"], quant) + a["bv"].astype(jnp.float32)
    q, k = rope(q, pos, d["theta"]), rope(k, pos, d["theta"])
    x = x + mm("shk,hkd->sd", attention(q, k, v, quant), a["wo"], quant)
    h = rmsnorm(x, p["ln2"], d["eps"])
    m = p["mlp"]
    if d["mlp"] == "swiglu":
        z = (jax.nn.silu(mm("sd,df->sf", h, m["wg"], quant))
             * mm("sd,df->sf", h, m["wu"], quant))
    elif d["mlp"] == "gelu":
        z = gelu_tanh(mm("sd,df->sf", h, m["wu"], quant))
    else:
        raise ValueError(d["mlp"])
    return x + mm("sf,fd->sd", z, m["wd"], quant)


def hidden(params, tokens, d, quant=None, remat=False):
    """Final-normed hidden states (S, D) of one sequence.  The stacked
    layer weights are scanned one layer at a time, each upcast to f32
    inside the loop, so a model larger than f32 memory still runs."""
    pos = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens].astype(jnp.float32)
    f = (lambda x, p: layer(x, p, pos, d, quant))
    if remat:
        f = jax.checkpoint(f)

    def body(x, p):
        return f(x, p), None

    x, _ = jax.lax.scan(body, x, params["blocks"]["p0"])
    return rmsnorm(x, params["final_norm"], d["eps"])


def head_of(params, d):
    return params["embed"].T if d["tied"] else params["lm_head"]


def logits_at(params, tokens, rows, d, quant=None):
    """Logits (len(rows), V) at positions ``rows`` of one sequence."""
    h = hidden(params, tokens, d, quant)[rows]
    return mm("sd,dv->sv", h, head_of(params, d), quant)


def row_loss_sum(params, tokens, d, quant=None, ce_block=512):
    """Summed next-token cross entropy of one sequence (S-1 targets),
    with layers and logit blocks recomputed in backward so one row fits."""
    h = hidden(params, tokens, d, quant, remat=True)[:-1]
    tgt = tokens[1:]
    head = head_of(params, d)

    @jax.checkpoint
    def block_nll(hb, tb):
        lg = mm("sd,dv->sv", hb, head, quant)
        lp = jax.nn.log_softmax(lg, -1)
        return -jnp.sum(jnp.take_along_axis(lp, tb[:, None], -1))

    n = h.shape[0]
    total = 0.0
    for lo in range(0, n, ce_block):
        total = total + block_nll(h[lo:lo + ce_block], tgt[lo:lo + ce_block])
    return total


def loss_and_grad(p32, tokens, d, quant=None):
    """Mean next-token cross entropy over a (B, S) batch and its gradient
    with respect to the f32 parameters ``p32``, one row at a time."""
    B, S = tokens.shape
    vg = jax.value_and_grad(lambda p, t: row_loss_sum(p, t, d, quant))

    def body(carry, row):
        tot, acc = carry
        l, g = vg(p32, row)
        return (tot + l, jax.tree.map(jnp.add, acc, g)), None

    zeros = jax.tree.map(jnp.zeros_like, p32)
    (tot, grads), _ = jax.lax.scan(body, (0.0, zeros), tokens)
    n = B * (S - 1)
    return tot / n, jax.tree.map(lambda g: g / n, grads)
