"""Neural-net layers in pure JAX: RMSNorm, RoPE, GQA attention (train +
decode), gated MLPs.

Decode attention is written so GSPMD can shard the KV-cache *sequence* dim:
scores/softmax/value-combine keep S as a contraction dim, letting XLA lower
the distributed-softmax (flash-decoding) pattern with small collectives.

Paged decode attention runs the Pallas kernel in ``repro.kernels`` (the
paper's "manually implemented well-optimized big operations") whenever the
backend is a TPU.  Full-sequence attention and RMSNorm route to their
kernels only when ``set_use_pallas(True)``: the flash kernel has no
backward pass yet, so training stays on the jnp path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.annotate import BATCH, ann

# flash attention + RMSNorm kernels on the full-sequence path (tests only)
_USE_PALLAS = False


def set_use_pallas(flag: bool):
    global _USE_PALLAS
    _USE_PALLAS = flag


# ---------------------------------------------------------------------------
# norms

def rmsnorm(x, weight, eps=1e-6):
    if _USE_PALLAS:
        from repro.kernels.ops import rmsnorm as k_rmsnorm
        return k_rmsnorm(x, weight, eps=eps)
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * (1.0 + weight.astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# RoPE

def rope_freqs(positions, head_dim, theta):
    """positions: int (...,) -> (cos, sin) of shape (..., head_dim//2)."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (..., S, H, hd); cos/sin: (..., S, hd//2) broadcastable."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]  # add head dim
    s = sin[..., None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)  # rotation in f32, stream stays bf16


# ---------------------------------------------------------------------------
# attention

def _softcap(scores, cap):
    if cap is None:
        return scores
    return jnp.tanh(scores / cap) * cap


# Above this many query rows, attention runs in unrolled query chunks so the
# (Sq, Sk) score matrix never materializes whole (flash-style blocking; the
# unrolled loop also keeps cost_analysis exact — lax.scan bodies are counted
# once by XLA's analysis).
ATTN_Q_CHUNK = 1024


def ring_selected(Sq: int) -> bool:
    """Should this full-sequence attention run on the ring schedule?

    ``PerfFlags.attn_impl``: "ring" forces it (degrades to one local block
    step without a mesh), "dense" forbids it, "auto" rings exactly when
    sequence sharding is on and the ambient mesh's "model" axis divides S
    (DESIGN.md §8).
    """
    from repro.perf_flags import FLAGS
    if FLAGS.attn_impl == "dense":
        return False
    if FLAGS.attn_impl == "ring":
        return True
    if not FLAGS.seq_shard:
        return False
    from repro.dist.compat import current_mesh
    mesh = current_mesh()
    n = dict(mesh.shape).get("model", 1) if mesh is not None else 1
    return n > 1 and Sq % n == 0


def gqa_attention(q, k, v, *, causal=True, window=None, softcap=None,
                  q_offset=0):
    """Grouped-query attention.

    q: (B, Sq, H, hd);  k, v: (B, Sk, K, hd) with H % K == 0.
    ``q_offset``: absolute position of q[0] (decode: cache length).
    ``window``: sliding window in tokens (None = full).
    """
    B, Sq, H, hd = q.shape

    if _USE_PALLAS and Sq > 1:
        from repro.kernels.ops import flash_attention
        return flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, q_offset=q_offset)

    from repro.perf_flags import FLAGS
    qc = FLAGS.attn_q_chunk
    if Sq > qc and FLAGS.attn_chunk_parallel:
        return _attention_chunk_parallel(q, k, v, causal=causal,
                                         window=window, softcap=softcap,
                                         q_offset=q_offset, qc=qc)
    if Sq > qc:
        Sk = k.shape[1]
        nc = (Sq + qc - 1) // qc
        outs = []
        for c in range(nc):
            lo = c * qc
            hi = min(Sq, lo + qc)
            kc, vc, k0 = k, v, 0
            if (FLAGS.window_slice and window is not None and causal
                    and q_offset == 0):
                # §Perf: keys outside [lo-window+1, hi) are masked anyway —
                # slice them out (static bounds): O(S·W) not O(S²)
                k0 = max(0, lo - window + 1)
                kend = min(Sk, hi)
                kc, vc = k[:, k0:kend], v[:, k0:kend]
            outs.append(_attention_dense(
                q[:, lo:hi], kc, vc, causal=causal, window=window,
                softcap=softcap, q_offset=q_offset + lo - k0))
        return jnp.concatenate(outs, axis=1)
    return _attention_dense(q, k, v, causal=causal, window=window,
                            softcap=softcap, q_offset=q_offset)


def _attention_chunk_parallel(q, k, v, *, causal, window, softcap,
                              q_offset, qc):
    """Blockwise attention with the q-chunk dim sharded over "model".

    All chunks compute in parallel across model ranks (k/v replicated);
    the output lands S-block-sharded, composing with the sequence-parallel
    residual stream.  Scores/probs per device are 1/|model| of the full
    (Sq, Sk) matrix.
    """
    from repro.perf_flags import FLAGS
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    scale = 1.0 / np.sqrt(hd)
    pad = (-Sq) % qc
    if pad:
        q = jnp.pad(q, [(0, 0), (0, pad), (0, 0), (0, 0)])
    nc = (Sq + pad) // qc
    qr = q.reshape(B, nc, qc, K, G, hd)
    qr = ann(qr, BATCH, "model", None, None, None, None)

    scores = jnp.einsum("bnqkgh,bskh->bnkgqs", qr.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = _softcap(scores, softcap)
    qpos = (jnp.arange(nc)[:, None] * qc + jnp.arange(qc)[None]) + q_offset
    kpos = jnp.arange(Sk)
    mask = jnp.ones((nc, qc, Sk), bool)
    if causal:
        mask &= kpos[None, None] <= qpos[:, :, None]
    if window is not None:
        mask &= kpos[None, None] > qpos[:, :, None] - window
    scores = jnp.where(mask[None, :, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    if FLAGS.probs_bf16:
        probs = probs.astype(q.dtype)
        out = jnp.einsum("bnkgqs,bskh->bnqkgh", probs, v)
    else:
        out = jnp.einsum("bnkgqs,bskh->bnqkgh", probs,
                         v.astype(jnp.float32))
    out = out.reshape(B, Sq + pad, H, hd)
    if pad:
        out = out[:, :Sq]
    return out.astype(q.dtype)


def _attention_dense(q, k, v, *, causal, window, softcap, q_offset):
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    scale = 1.0 / np.sqrt(hd)
    qg = q.reshape(B, Sq, K, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = _softcap(scores, softcap)

    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    from repro.perf_flags import FLAGS
    if FLAGS.attn_probs_seq_shard:
        scores = ann(scores, BATCH, None, None, None, "model")
    probs = jax.nn.softmax(scores, axis=-1)
    if FLAGS.attn_probs_seq_shard:
        probs = ann(probs, BATCH, None, None, None, "model")
    if FLAGS.probs_bf16:
        # §Perf: f32 softmax, bf16 PV matmul (halves the probs buffers)
        out = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(q.dtype), v)
    else:
        out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v.astype(jnp.float32))
    if FLAGS.attn_probs_seq_shard:
        # pin the per-chunk PV output REPLICATED over model so the S-sharded
        # probs contract locally (partial-sum + small all-reduce) instead of
        # the partitioner replicating the whole probs tensor per chunk
        out = ann(out, BATCH, None, None, None, None)
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, softcap=None):
    """One-token attention against a (possibly ring-buffered) KV cache.

    q: (B, 1, H, hd); caches: (B, S, K, hd); cache_len: filled length —
    a scalar (lockstep batch) or a (B,) vector (per-sequence lengths,
    mixed-length serving). Positions >= cache_len are masked out.
    S is a pure contraction dim — shard it and GSPMD emits the
    flash-decoding distributed softmax.
    """
    B, _, H, hd = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = 1.0 / np.sqrt(hd)
    qg = q.reshape(B, K, G, hd)
    scores = jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale
    scores = _softcap(scores, softcap)
    kpos = jnp.arange(S)
    # (1, S) or (B, S) valid map, broadcast over the (K, G) head dims
    valid = kpos[None, :] < jnp.asarray(cache_len).reshape(-1, 1)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", probs, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           k_scale=None, v_scale=None, window=None,
                           softcap=None):
    """One-token attention through the paged pool (DESIGN.md §9).

    q: (B, H, hd); pools: (NB, bs, K, hd); block_tables: (B, P);
    lengths: (B,) live tokens including the current one.  Runs the
    compiled Pallas paged kernel when the backend is a TPU; elsewhere the
    gather-based oracle is the fast path (interpret-mode Pallas runs the
    grid in Python).
    ``k_scale``/``v_scale``: (NB, bs, K) f32 per-row scales when the
    pools are quantized (DESIGN.md §13); both paths fuse the dequant into
    attention — no full-precision cache copy.
    """
    if jax.default_backend() == "tpu":
        from repro.kernels.ops import paged_attention
        return paged_attention(q, k_pages, v_pages, block_tables, lengths,
                               k_scale=k_scale, v_scale=v_scale,
                               window=window, softcap=softcap)
    from repro.kernels.ref import paged_attention_ref
    return paged_attention_ref(q, k_pages, v_pages, block_tables, lengths,
                               k_scale=k_scale, v_scale=v_scale,
                               window=window, softcap=softcap)


def paged_context_attention(q, k_ctx, v_ctx, *, q_offset, kv_len,
                            window=None, softcap=None):
    """Chunked-prefill attention against gathered paged context.

    q: (B, C, H, hd) — the prompt chunk's queries; k_ctx/v_ctx:
    (B, S_ctx, K, hd) in logical position order (the chunk's own rows
    already written to the pool and gathered back); ``q_offset``:
    absolute position of q[:, 0]; ``kv_len``: live tokens after this
    chunk.  Both scalars or (B,) vectors.  Dense masked attention in
    f32 — prefill is compute-bound, the paged kernel targets decode.
    """
    B, C, H, hd = q.shape
    Sk, K = k_ctx.shape[1], k_ctx.shape[2]
    G = H // K
    scale = 1.0 / np.sqrt(hd)
    qg = q.reshape(B, C, K, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                        k_ctx.astype(jnp.float32)) * scale
    scores = _softcap(scores, softcap)
    qpos = (jnp.asarray(q_offset).reshape(-1, 1)
            + jnp.arange(C)[None])                     # (B or 1, C)
    kpos = jnp.arange(Sk)
    mask = kpos[None, None] <= qpos[..., None]         # causal
    mask &= kpos[None, None] < jnp.asarray(kv_len).reshape(-1, 1, 1)
    if window is not None:
        mask &= kpos[None, None] > qpos[..., None] - window
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v_ctx.astype(jnp.float32))
    return out.reshape(B, C, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# attention block (projections + rope + attn + out-proj)

def attn_project_qkv(p, x, cfg):
    B, S, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    from repro.perf_flags import FLAGS
    if FLAGS.seq_shard:
        # sequence sharding (DESIGN.md §8): q/k/v stay S-sharded over
        # "model" — GQA's small K never has to divide the model axis, and
        # the ring schedule consumes exactly this layout
        q = ann(q, BATCH, "model", None, None)
        k = ann(k, BATCH, "model", None, None)
        v = ann(v, BATCH, "model", None, None)
    else:
        # megatron: batch over data axes, heads over model (ann drops an
        # axis when the dim is not divisible, e.g. kv=8 heads on a 16-way
        # model axis)
        q = ann(q, BATCH, None, "model", None)
        k = ann(k, BATCH, None, "model", None)
        v = ann(v, BATCH, None, "model", None)
    return q, k, v


def attn_block(p, x, cfg, spec, positions=None, rope=True):
    """Full-sequence attention block (training / prefill).

    Returns (out, (k, v)) — the kv tensors become the prefill cache.
    """
    B, S, D = x.shape
    q, k, v = attn_project_qkv(p, x, cfg)
    if rope:
        pos = positions if positions is not None else jnp.arange(S)
        cos, sin = rope_freqs(pos, cfg.hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    causal = spec.attn != "bidir"
    if causal and S > 1 and ring_selected(S):
        # sequence-sharded ring schedule (DESIGN.md §8): S stays sharded
        # over "model" end to end; per-device attention state is O(S·S/P)
        from repro.dist.ring import ring_attention
        out = ring_attention(q, k, v, causal=True, window=spec.window,
                             softcap=cfg.attn_softcap,
                             inner="pallas" if _USE_PALLAS else "jnp")
        out = ann(out, BATCH, "model", None, None)
    else:
        out = gqa_attention(q, k, v, causal=causal,
                            window=spec.window, softcap=cfg.attn_softcap)
        out = ann(out, BATCH, None, "model", None)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    # sequence-parallel output: the heads-contraction all-reduce becomes a
    # reduce-scatter over S (a no-op re-pin on the ring path, which is
    # already S-sharded)
    return ann(out, BATCH, "model", None), (k, v)


def attn_block_decode(p, x, cache_k, cache_v, pos, cfg, spec):
    """Single-token decode step. x: (B, 1, D); caches: (B, S, K, hd);
    pos: absolute position — scalar (lockstep batch) or (B,) vector
    (per-sequence lengths). Returns (out, new_k_cache, new_v_cache).
    For windowed layers the cache is a ring buffer of size ``window``."""
    q, k, v = attn_project_qkv(p, x, cfg)
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        cos, sin = rope_freqs(pos[None], cfg.hd, cfg.rope_theta)
        cos, sin = cos[None], sin[None]          # (1, 1, hd//2), broadcast B
    else:
        cos, sin = rope_freqs(pos[:, None], cfg.hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    B, S, K, hd = cache_k.shape
    slot = pos % S  # ring for windowed caches; identity else
    if pos.ndim == 0:
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, slot,
                                                      axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, slot,
                                                      axis=1)
    else:
        # per-sequence write slots: one scatter over the flattened (B, S)
        idx = jnp.arange(B) * S + slot
        cache_k = cache_k.reshape(B * S, K, hd).at[idx].set(
            k[:, 0]).reshape(B, S, K, hd)
        cache_v = cache_v.reshape(B * S, K, hd).at[idx].set(
            v[:, 0]).reshape(B, S, K, hd)
    cache_len = jnp.minimum(pos + 1, S)
    # NOTE: windowing is enforced by ring-buffer SIZING (cache ring == window
    # for windowed layers), not by a position mask — ring slots are not in
    # position order.
    out = decode_attention(q, cache_k, cache_v, cache_len,
                           softcap=cfg.attn_softcap)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return out, cache_k, cache_v


def attn_block_decode_paged(p, x, cache, block_tables, pos, cfg, spec):
    """Single-token decode through the paged pool. x: (B, 1, D); cache:
    layer dict with "k"/"v" (NB, bs, K, hd) pools (plus "k_scale"/
    "v_scale" (NB, bs, K) f32 when the pools are quantized, DESIGN.md
    §13); block_tables: (B, P); pos: (B,) absolute position of the
    incoming token.  Writes the token's k/v into its block-table slot
    (quantizing on append), then attends through the table.  Returns
    (out, new_cache).  Inactive lanes must carry sink tables (pos 0,
    table 0) so their writes land in the sink block."""
    k_pages, v_pages = cache["k"], cache["v"]
    quantized = "k_scale" in cache
    q, k, v = attn_project_qkv(p, x, cfg)
    cos, sin = rope_freqs(pos[:, None], cfg.hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    NB, bs, K, hd = k_pages.shape
    B = q.shape[0]
    page = block_tables[jnp.arange(B), pos // bs]        # physical block
    idx = page * bs + pos % bs
    k_row, v_row = k[:, 0], v[:, 0]                      # (B, K, hd)
    scales = {}
    if quantized:
        from repro.kernels.quant import kv_quantize_rows
        k_row, ks_row = kv_quantize_rows(k_row, k_pages.dtype)
        v_row, vs_row = kv_quantize_rows(v_row, v_pages.dtype)
        scales = {
            "k_scale": cache["k_scale"].reshape(NB * bs, K).at[idx].set(
                ks_row).reshape(NB, bs, K),
            "v_scale": cache["v_scale"].reshape(NB * bs, K).at[idx].set(
                vs_row).reshape(NB, bs, K)}
    k_pages = k_pages.reshape(NB * bs, K, hd).at[idx].set(
        k_row.astype(k_pages.dtype)).reshape(NB, bs, K, hd)
    v_pages = v_pages.reshape(NB * bs, K, hd).at[idx].set(
        v_row.astype(v_pages.dtype)).reshape(NB, bs, K, hd)
    out = paged_decode_attention(q[:, 0], k_pages, v_pages, block_tables,
                                 pos + 1, window=spec.window,
                                 softcap=cfg.attn_softcap, **scales)
    out = jnp.einsum("bshk,hkd->bsd", out[:, None], p["wo"])
    return out, {"k": k_pages, "v": v_pages, **scales}


def cross_attn_block(p, x, enc_kv, cfg):
    """Decoder cross-attention to encoder output (whisper)."""
    B, S, D = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k, v = enc_kv
    out = gqa_attention(q, k, v, causal=False)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# ---------------------------------------------------------------------------
# MLPs

def mlp_block(p, x, kind):
    hid = lambda h: ann(h, BATCH, None, "model")   # F over model
    if kind == "swiglu":
        h = hid(jax.nn.silu(x @ p["wg"]) * (x @ p["wu"]))
    elif kind == "geglu":
        h = hid(jax.nn.gelu(x @ p["wg"], approximate=True) * (x @ p["wu"]))
    elif kind == "gelu":
        h = hid(jax.nn.gelu(x @ p["wu"], approximate=True))
    else:
        raise ValueError(kind)
    return ann(h @ p["wd"], BATCH, "model", None)  # sequence-parallel out
