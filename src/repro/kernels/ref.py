"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def flash_attention_ref(q, k, v, *, causal=True, window=None, softcap=None,
                        q_offset=0, kv_len=None):
    """q: (B, Sq, H, hd); k/v: (B, Sk, K, hd), H % K == 0. f32 math."""
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    kk = jnp.repeat(k, G, axis=2).astype(jnp.float32)
    vv = jnp.repeat(v, G, axis=2).astype(jnp.float32)
    s = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32), kk) / np.sqrt(hd)
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Sk)
    m = jnp.ones((Sq, Sk), bool)
    if causal:
        m &= kpos[None] <= qpos[:, None]
    if window is not None:
        m &= kpos[None] > qpos[:, None] - window
    if kv_len is not None:
        m &= (kpos < kv_len)[None]
    s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqs,bshd->bqhd", p, vv).astype(q.dtype)


def paged_attention_ref(q, k_pages, v_pages, block_tables, lengths, layer,
                        *, k_scale=None, v_scale=None, window=None,
                        softcap=None, scale=None):
    """q: (B, H, hd); pools: (L, NB, bs, K*hd) stacked lane-dense;
    block_tables: (B, P) int32; lengths: (B,) live tokens incl. the
    current one; layer: which of the L layers to read.  Gathers the
    layer's logical KV through the table (only the table's pages, never
    the whole layer), then masked dense attention in f32.  This is also
    the CPU fast path the serving engine uses (interpret-mode Pallas is
    per-grid-step Python).

    ``k_scale``/``v_scale``: (L, NB, bs, K) f32 per-(token, kv-head)
    scales for quantized pools (DESIGN.md §13) — rows dequantize as
    ``row.astype(f32) * scale`` before attention.  ``scale``: the score
    scale (None: hd^-0.5)."""
    B, H, hd = q.shape
    bs = k_pages.shape[2]
    K = k_pages.shape[3] // hd
    G = H // K
    P = block_tables.shape[1]
    # (B, P, bs, K*hd) -> (B, P*bs, K, hd): logical position order
    k = k_pages[layer, block_tables].reshape(B, P * bs, K, hd)
    v = v_pages[layer, block_tables].reshape(B, P * bs, K, hd)
    if k_scale is not None:
        from .quant import kv_dequantize
        ks = k_scale[layer, block_tables].reshape(B, P * bs, K)
        vs = v_scale[layer, block_tables].reshape(B, P * bs, K)
        k, v = kv_dequantize(k, ks), kv_dequantize(v, vs)
    qg = q.reshape(B, K, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32))
    s = s / np.sqrt(hd) if scale is None else s * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    kpos = jnp.arange(P * bs)
    mask = kpos[None] < lengths[:, None]                  # (B, S)
    if window is not None:
        mask &= kpos[None] > (lengths[:, None] - 1) - window
    s = jnp.where(mask[:, None, None], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m) * (mask[:, None, None])
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)                   # empty lane -> 0
    out = jnp.einsum("bkgs,bskh->bkgh", p, v.astype(jnp.float32))
    return out.reshape(B, H, hd).astype(q.dtype)


def expert_gmm_ref(x, wg, wu, wd, tile_group, n_tiles, *, block_rows):
    """Oracle for ``kernels.expert_gmm.expert_gmm``: each row of the
    tiles before ``n_tiles`` through its tile's expert's SwiGLU,
    ``silu(x Wg) * (x Wu) Wd`` with f32 accumulation; rows of later tiles
    are zeros (the kernel leaves them unwritten).  Every expert is applied
    to every row and masked, which is fine at oracle sizes and is the CPU
    path the expert layer takes."""
    M = x.shape[0]
    row_e = jnp.repeat(tile_group, block_rows, total_repeat_length=M)
    live = jnp.arange(M) < n_tiles * block_rows
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(wg.shape[0]):
        g = jnp.dot(x, wg[e], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu[e], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        y = jnp.dot(h, wd[e], preferred_element_type=jnp.float32)
        out = jnp.where(((row_e == e) & live)[:, None], y, out)
    return out.astype(x.dtype)


def rmsnorm_ref(x, weight, eps=1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * (1.0 + weight.astype(jnp.float32))).astype(dt)


def sample_ref(logits, u, *, temperature=1.0, top_k=None, top_p=None):
    """Oracle for ``kernels.sampling.sample_tokens``: top-k / top-p /
    inverse-CDF sampling in dense jnp with the kernel's exact tie rules.

    logits: (B, V); u: (B,) uniforms in [0, 1).  Returns (B,) int32.
    Top-p uses the per-token strict-mass predicate (keep x iff the mass
    strictly above x is < top_p * Z) via an O(V^2) pairwise sum — tie
    classes are kept or dropped whole, unlike the usual sorted-cumsum
    formulation that splits them arbitrarily.  Fine for oracle-sized V.
    """
    B, V = logits.shape
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    x = logits.astype(jnp.float32) / temperature
    keep = jnp.ones_like(x, bool)
    if top_k is not None and 0 < top_k < V:
        kth = jax.lax.top_k(x, top_k)[0][:, -1:]
        keep &= x >= kth
    m = jnp.max(x, axis=-1, keepdims=True)
    p = jnp.where(keep, jnp.exp(x - m), 0.0)
    if top_p is not None and top_p < 1.0:
        budget = top_p * jnp.sum(p, axis=-1, keepdims=True)
        strictly_above = x[:, None, :] > x[:, :, None]        # (B, V, V)
        mass_above = jnp.sum(strictly_above * p[:, None, :], axis=-1)
        p = jnp.where(mass_above < budget, p, 0.0)
    c = jnp.cumsum(p, axis=-1)
    target = u.astype(jnp.float32)[:, None] * c[:, -1:]
    return jnp.argmax(c > target, axis=-1).astype(jnp.int32)


def sgd_momentum_ref(param, grad, mom, *, lr, mu, weight_decay):
    """The KVStore updater as a fused mutating op (fp32 momentum master)."""
    g32 = grad.astype(jnp.float32) + weight_decay * param.astype(jnp.float32)
    mom_new = mu * mom + g32
    p_new = (param.astype(jnp.float32) - lr * mom_new).astype(param.dtype)
    return p_new, mom_new
