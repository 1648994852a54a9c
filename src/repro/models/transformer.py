"""Generic pattern-based transformer LM covering all assigned families:
dense GQA, MoE, SSM (mamba2), hybrid (jamba), VLM prefix (internvl2) and
enc-dec (whisper).

Layers repeat a *pattern* of LayerSpecs; same-position blocks are stacked
on a leading n_super axis and run under ``lax.scan`` (small HLO at 80L).

Entry points:
  init_params(cfg, key)             real weights (smoke tests)
  loss_fn(cfg)(params, batch)       next-token CE + MoE aux
  prefill_fn(cfg)(params, batch)    forward + KV/SSM cache construction
  decode_fn(cfg)(params, cache, batch, pos)   one-token serve step
  make_cache(cfg, B, cache_len)     zeroed cache pytree
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.annotate import BATCH, ann

from .common import ArchConfig, LayerSpec
from .layers import (attn_block, attn_block_decode, attn_block_decode_paged,
                     attn_project_qkv, apply_rope, cross_attn_block,
                     mlp_block, paged_append, paged_context_attention,
                     paged_gather, rmsnorm, rope_freqs)
from .moe import moe_block
from .ssm import mamba_block


# ---------------------------------------------------------------------------
# init

def _dense(key, shape, dtype, scale=None):
    scale = scale or (1.0 / np.sqrt(shape[0]))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_attn_params(key, cfg: ArchConfig, cross=False):
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = cfg.activation_dtype()
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense(ks[0], (D, H, hd), dt),
        "wk": _dense(ks[1], (D, K, hd), dt),
        "wv": _dense(ks[2], (D, K, hd), dt),
        "wo": _dense(ks[3], (H, hd, D), dt, scale=1.0 / np.sqrt(H * hd)),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((H, hd), dt)
        p["bk"] = jnp.zeros((K, hd), dt)
        p["bv"] = jnp.zeros((K, hd), dt)
    return p


def init_mlp_params(key, cfg: ArchConfig, kind: str):
    D, F = cfg.d_model, cfg.d_ff
    dt = cfg.activation_dtype()
    ks = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        return {"wg": _dense(ks[0], (D, F), dt), "wu": _dense(ks[1], (D, F), dt),
                "wd": _dense(ks[2], (F, D), dt)}
    return {"wu": _dense(ks[0], (D, F), dt), "wd": _dense(ks[1], (F, D), dt)}


def init_moe_params(key, cfg: ArchConfig):
    """The router over all ``n_experts``; the routed experts' weights for
    the ``n_held`` this chip holds; the shared expert at its own width."""
    D, F, E, Eh = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.n_held
    dt = cfg.activation_dtype()
    ks = jax.random.split(key, 7)
    p = {"router": _dense(ks[0], (D, E), jnp.float32),
         "wg": _dense(ks[1], (Eh, D, F), dt, scale=1.0 / np.sqrt(D)),
         "wu": _dense(ks[2], (Eh, D, F), dt, scale=1.0 / np.sqrt(D)),
         "wd": _dense(ks[3], (Eh, F, D), dt, scale=1.0 / np.sqrt(F))}
    if cfg.shared_expert:
        Fs = cfg.d_shared
        p["shared_wg"] = _dense(ks[4], (D, Fs), dt)
        p["shared_wu"] = _dense(ks[5], (D, Fs), dt)
        p["shared_wd"] = _dense(ks[6], (Fs, D), dt)
    return p


def init_mamba_params(key, cfg: ArchConfig):
    D, di, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    W = cfg.conv_width
    ch = di + 2 * N
    dt = cfg.activation_dtype()
    ks = jax.random.split(key, 3)
    p = {
        "in_proj": _dense(ks[0], (D, 2 * di + 2 * N + H), dt),
        "conv_w": _dense(ks[1], (W, ch), dt, scale=1.0 / np.sqrt(W)),
        "conv_b": jnp.zeros((ch,), dt),
        "dt_bias": jnp.full((H,), -2.0, jnp.float32),
        "A_log": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "out_proj": _dense(ks[2], (di, D), dt),
    }
    if cfg.ssm_gated_norm:
        p["norm"] = jnp.zeros((di,), dt)     # offset from one, as ln1/ln2
    return p


def init_block_params(key, cfg: ArchConfig, spec: LayerSpec):
    dt = cfg.activation_dtype()
    D = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {"ln1": jnp.zeros((D,), dt)}
    if spec.kind == "attn":
        p["attn"] = init_attn_params(ks[0], cfg)
    else:
        p["ssm"] = init_mamba_params(ks[0], cfg)
    if spec.cross_attn:
        p["ln_x"] = jnp.zeros((D,), dt)
        p["xattn"] = init_attn_params(ks[2], cfg, cross=True)
    if spec.mlp != "none":
        p["ln2"] = jnp.zeros((D,), dt)
        p["moe" if spec.mlp == "moe" else "mlp"] = (
            init_moe_params(ks[1], cfg) if spec.mlp == "moe"
            else init_mlp_params(ks[1], cfg, spec.mlp))
    if cfg.sandwich_norm:
        p["ln1_post"] = jnp.zeros((D,), dt)
        if spec.mlp != "none":
            p["ln2_post"] = jnp.zeros((D,), dt)
    return p


def init_params(cfg: ArchConfig, key):
    dt = cfg.activation_dtype()
    keys = jax.random.split(key, 8)
    params = {"embed": _dense(keys[0], (cfg.vocab, cfg.d_model), dt, scale=0.02),
              "final_norm": jnp.zeros((cfg.d_model,), dt)}
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense(keys[1], (cfg.d_model, cfg.vocab), dt)

    blocks = {}
    for i, spec in enumerate(cfg.pattern):
        bkeys = jax.random.split(jax.random.fold_in(keys[2], i), cfg.n_super)
        blocks[f"p{i}"] = jax.vmap(
            lambda k: init_block_params(k, cfg, spec))(bkeys)
    params["blocks"] = blocks

    if cfg.encoder_layers:  # whisper encoder stack (bidir attn + gelu mlp)
        espec = LayerSpec(kind="attn", attn="bidir", mlp="gelu")
        ekeys = jax.random.split(keys[3], cfg.encoder_layers)
        params["encoder"] = jax.vmap(
            lambda k: init_block_params(k, cfg, espec))(ekeys)
        params["enc_final_norm"] = jnp.zeros((cfg.d_model,), dt)
    if cfg.frontend_tokens:  # modality projector stub (VLM / audio)
        params["frontend_proj"] = _dense(keys[4], (cfg.frontend_dim,
                                                   cfg.d_model), dt)
    return params


# ---------------------------------------------------------------------------
# forward blocks

def _res(h, cfg: ArchConfig):
    """A sublayer's output as it joins the residual stream: scaled by
    ``residual_multiplier`` where the configuration has one."""
    if cfg.residual_multiplier != 1.0:
        return h * cfg.residual_multiplier
    return h


def _add_counts(counts: dict, aux: dict) -> dict:
    """Fold one expert layer's row counts into the step's: rows summed,
    the busiest held expert's rows by max."""
    if not counts or "rows" not in aux:
        return counts
    return {"expert_rows": counts["expert_rows"] + aux["rows"],
            "expert_rows_max": jnp.maximum(counts["expert_rows_max"],
                                           aux["rows_max"])}


def apply_block(p, x, cfg: ArchConfig, spec: LayerSpec, enc_kv=None,
                positions=None, lengths=None):
    """Full-sequence block (train / prefill). Returns (x, cache, aux).

    ``lengths``: (B,) live lengths of a tail-padded mixed-length prefill —
    causal masking already hides pads from attention, but the SSM scan is
    recurrent: without masking, pad tokens would evolve the cached state.
    """
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    if spec.kind == "attn":
        from repro.perf_flags import FLAGS
        if FLAGS.attn_gather_once and not FLAGS.seq_shard:
            # §Perf: one explicit bf16 gather of the sequence-parallel
            # stream before the three qkv einsums (not three, never f32).
            # Under seq_shard the stream must *stay* S-sharded (the ring
            # path never gathers S), so the flag is a no-op there.
            h = ann(h, BATCH, None, None)
        h, kv = attn_block(p["attn"], h, cfg, spec, positions=positions)
        cache = {"k": kv[0], "v": kv[1]}
    else:
        h, (conv_s, ssm_s) = mamba_block(p["ssm"], h, cfg, valid_len=lengths)
        cache = {"conv": conv_s, "ssm": ssm_s}
    if cfg.sandwich_norm:
        h = rmsnorm(h, p["ln1_post"], cfg.norm_eps)
    x = x + _res(h, cfg)

    if spec.cross_attn:
        h = rmsnorm(x, p["ln_x"], cfg.norm_eps)
        h = cross_attn_block(p["xattn"], h, enc_kv, cfg)
        x = x + _res(h, cfg)

    aux = {"load_balance": jnp.zeros((), jnp.float32),
           "router_z": jnp.zeros((), jnp.float32)}
    if spec.mlp != "none":
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        if spec.mlp == "moe":
            h, aux = moe_block(p["moe"], h, cfg)
            aux = {k: aux[k].astype(jnp.float32)
                   for k in ("load_balance", "router_z")}
        else:
            h = mlp_block(p["mlp"], h, spec.mlp)
        if cfg.sandwich_norm:
            h = rmsnorm(h, p["ln2_post"], cfg.norm_eps)
        x = x + _res(h, cfg)
    return x, cache, aux


def _decode_ssm_paged(p, h, states, layer, active, cfg: ArchConfig):
    """One token through an SSM layer whose lanes' states lie in layer
    ``layer`` of the stacked slot states (n_super, B, ...), which the
    layer scan carries: the step reads the layer's states and writes its
    new ones back where they lie, the decoding lanes' new and the others'
    as they were (DESIGN.md §16)."""
    conv0 = jax.lax.dynamic_index_in_dim(states["conv"], layer, 0, False)
    ssm0 = jax.lax.dynamic_index_in_dim(states["ssm"], layer, 0, False)
    h, (conv_s, ssm_s) = mamba_block(p, h, cfg, conv_state=conv0,
                                     ssm_state=ssm0, decode=True)
    conv_s = jnp.where(active[:, None, None], conv_s, conv0)
    ssm_s = jnp.where(active[:, None, None, None], ssm_s, ssm0)
    at = (layer, 0, 0, 0)
    return h, {
        "conv": jax.lax.dynamic_update_slice(
            states["conv"], conv_s[None].astype(states["conv"].dtype), at),
        "ssm": jax.lax.dynamic_update_slice(
            states["ssm"], ssm_s[None].astype(states["ssm"].dtype),
            at + (0,))}


def apply_block_decode(p, x, cache, pos, cfg: ArchConfig, spec: LayerSpec,
                       enc_kv=None, block_tables=None, active=None,
                       layer=None):
    """One-token block step.  Returns (x, new_cache, aux): ``aux`` is an
    expert layer's (its router losses and row counts), else empty.

    ``layer`` switches the block to the paged cache's stacked layout:
    attention reads and writes layer ``layer`` of the stacked pools dict,
    "k"/"v" (L, NB, bs, K*hd), through ``block_tables``, with ``pos`` the
    (B,) per-sequence position vector; an SSM block reads and writes
    layer ``layer`` of the stacked slot states, and ``active``: (B,) bool
    marks the lanes decoding this step — the others (empty slots,
    requests still mid-prefill) keep their recurrent states untouched,
    and their attention writes already land in the sink block."""
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    if spec.kind == "attn" and block_tables is not None:
        h, new_cache = attn_block_decode_paged(p["attn"], h, cache, layer,
                                               block_tables, pos, cfg, spec)
    elif spec.kind == "attn":
        h, ck, cv = attn_block_decode(p["attn"], h, cache["k"], cache["v"],
                                      pos, cfg, spec)
        new_cache = {"k": ck, "v": cv}
    elif layer is not None:
        h, new_cache = _decode_ssm_paged(p["ssm"], h, cache, layer, active,
                                         cfg)
    else:
        h, (conv_s, ssm_s) = mamba_block(p["ssm"], h, cfg,
                                         conv_state=cache["conv"],
                                         ssm_state=cache["ssm"], decode=True)
        new_cache = {"conv": conv_s, "ssm": ssm_s}
    if cfg.sandwich_norm:
        h = rmsnorm(h, p["ln1_post"], cfg.norm_eps)
    x = x + _res(h, cfg)
    if spec.cross_attn:
        h = rmsnorm(x, p["ln_x"], cfg.norm_eps)
        h = cross_attn_block(p["xattn"], h, enc_kv, cfg)
        x = x + _res(h, cfg)
    aux = {}
    if spec.mlp != "none":
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        if spec.mlp == "moe":
            # lanes that are not decoding route no rows
            h, aux = moe_block(p["moe"], h, cfg, valid_len=(
                None if active is None else active.astype(jnp.int32)))
        else:
            h = mlp_block(p["mlp"], h, spec.mlp)
        if cfg.sandwich_norm:
            h = rmsnorm(h, p["ln2_post"], cfg.norm_eps)
        x = x + _res(h, cfg)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# whisper encoder

def run_encoder(params, frames, cfg: ArchConfig):
    """frames: (B, T_enc, frontend_dim) stub embeddings -> (B, T_enc, D)."""
    x = frames.astype(cfg.activation_dtype()) @ params["frontend_proj"]
    espec = LayerSpec(kind="attn", attn="bidir", mlp="gelu")

    def body(x, p):
        x, _, _ = apply_block(p, x, cfg, espec)
        return x, None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return rmsnorm(x, params["enc_final_norm"], cfg.norm_eps)


def encoder_cross_kv(params, enc_out, cfg):
    """Precompute per-(pattern-position) cross K/V from encoder output."""
    kvs = {}
    for i, spec in enumerate(cfg.pattern):
        if not spec.cross_attn:
            continue
        bp = params["blocks"][f"p{i}"]

        def kv(bp_i):
            k = jnp.einsum("bsd,dhk->bshk", enc_out, bp_i["xattn"]["wk"])
            v = jnp.einsum("bsd,dhk->bshk", enc_out, bp_i["xattn"]["wv"])
            return k, v
        kvs[f"p{i}"] = jax.vmap(kv)(bp)  # stacked over n_super
    return kvs


# ---------------------------------------------------------------------------
# full model

def embed_tokens(params, tokens, cfg):
    x = params["embed"][tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    # residual stream: batch over data axes, SEQUENCE over "model" between
    # blocks (sequence parallelism: the saved/remat activations are 1/|model|
    # the size; attention/MLP gather S and return reduce-scattered partials)
    return ann(x.astype(cfg.activation_dtype()), BATCH, "model", None)


def final_logits(params, x, cfg):
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head).astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.final_softcap:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    return logits


def run_stack(params, x, cfg: ArchConfig, enc_kvs=None, positions=None,
              collect_cache=False, lengths=None):
    """Scan the super-block stack. Returns (x, caches, aux_totals)."""
    pattern = cfg.pattern

    def body(carry, xs):
        x, lb, rz = carry
        x = ann(x, BATCH, "model", None)   # sequence-parallel between blocks
        bp = xs["params"]
        caches = {}
        for i, spec in enumerate(pattern):
            enc_kv = None
            if spec.cross_attn and enc_kvs is not None:
                enc_kv = xs["enc"][f"p{i}"]
            x, cache, aux = apply_block(bp[f"p{i}"], x, cfg, spec,
                                        enc_kv=enc_kv, positions=positions,
                                        lengths=lengths)
            caches[f"p{i}"] = cache
            lb = lb + aux["load_balance"]
            rz = rz + aux["router_z"]
        out = caches if collect_cache else None
        return (x, lb, rz), out

    if cfg.remat:
        # save only each super-block's input (x, carry); recompute the rest
        # in backward — the remat analogue of §3.1 memory planning
        body = jax.checkpoint(body, prevent_cse=False)

    xs = {"params": params["blocks"]}
    if enc_kvs is not None:
        xs["enc"] = enc_kvs
    if cfg.n_super <= 4:
        # unrolled: exact cost_analysis for the roofline probes (scan bodies
        # are counted once by XLA's analysis)
        carry = (x, 0.0, 0.0)
        ys = []
        for i in range(cfg.n_super):
            carry, y = body(carry, jax.tree.map(lambda a: a[i], xs))
            ys.append(y)
        (x, lb, rz) = carry
        caches = (jax.tree.map(lambda *a: jnp.stack(a), *ys)
                  if collect_cache else None)
    else:
        (x, lb, rz), caches = jax.lax.scan(body, (x, 0.0, 0.0), xs)
    return x, caches, {"load_balance": lb, "router_z": rz}


def _pipeline_stage_fn(cfg: ArchConfig):
    """One pipeline stage: apply this stage's super-block slice.

    Returns ``stage_fn(blocks_slice, x) -> (x, aux)`` where ``aux`` holds
    the MoE scalar losses of the slice (summed over its super-blocks).
    The per-super-block body is the train-path subset of ``run_stack``'s
    (no cache collection, no enc-dec cross-attention).
    """
    pattern = cfg.pattern

    def body(carry, bp):
        x, lb, rz = carry
        # sequence-parallel between blocks, like run_stack; identity
        # inside the stage shard_map (annotations suppressed) but live on
        # the pp-requested-without-stage-axis GSPMD fallback
        x = ann(x, BATCH, "model", None)
        for i, spec in enumerate(pattern):
            x, _, aux = apply_block(bp[f"p{i}"], x, cfg, spec)
            lb = lb + aux["load_balance"]
            rz = rz + aux["router_z"]
        return (x, lb, rz), None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)

    def stage_fn(blocks_local, x):
        n_local = jax.tree.leaves(blocks_local)[0].shape[0]
        carry = (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
        if n_local <= 4:
            for i in range(n_local):
                carry, _ = body(carry,
                                jax.tree.map(lambda a: a[i], blocks_local))
        else:
            carry, _ = jax.lax.scan(body, carry, blocks_local)
        x, lb, rz = carry
        return x, {"load_balance": lb, "router_z": rz}

    return stage_fn


def run_stack_pipelined(params, x, cfg: ArchConfig):
    """The super-block stack as per-stage scans under the 1F1B pipeline
    (DESIGN.md §10): each ``stage`` mesh shard holds a layer-contiguous
    slice of the stacked block params and microbatches stream through
    ``dist.pipeline.pipeline_stack``.  Train path only: caches and
    enc-dec cross-attention are not carried.  Returns (x, aux_totals)."""
    from repro.dist.pipeline import pipeline_stack, validate_pipeline
    from repro.perf_flags import FLAGS
    if cfg.encoder_layers:
        raise ValueError(
            "pipeline parallelism does not support enc-dec archs: the "
            "decoder's cross-attention KV is per-super-block state the "
            "stage hand-off does not carry (DESIGN.md §10)")
    validate_pipeline(n_stages=FLAGS.pp_stages,
                      microbatches=FLAGS.microbatches, n_super=cfg.n_super,
                      batch=x.shape[0], seq_shard=FLAGS.seq_shard)
    stage_fn = _pipeline_stage_fn(cfg)
    x, aux = pipeline_stack(stage_fn, params["blocks"], x,
                            microbatches=FLAGS.microbatches)
    return x, aux


def forward_loss(params, batch, cfg: ArchConfig):
    """Next-token CE loss. batch: tokens (B,S) [+ patches/frames]."""
    from repro.perf_flags import FLAGS
    tokens = batch["tokens"]
    x = embed_tokens(params, tokens, cfg)
    prefix = 0
    enc_kvs = None
    if cfg.encoder_layers:                      # whisper: enc-dec
        enc_out = run_encoder(params, batch["frames"], cfg)
        enc_kvs = encoder_cross_kv(params, enc_out, cfg)
    elif cfg.frontend_tokens:                   # VLM: prefix patch embeds
        pre = batch["patches"].astype(cfg.activation_dtype()) \
            @ params["frontend_proj"]
        x = jnp.concatenate([pre, x], axis=1)
        prefix = pre.shape[1]

    if FLAGS.pp_stages > 1:
        # microbatches alone (pp_stages == 1) are a no-op: without a
        # stage axis the schedule is the plain stack, so keep run_stack's
        # layout annotations and enc-dec support
        x, aux = run_stack_pipelined(params, x, cfg)
    else:
        x, _, aux = run_stack(params, x, cfg, enc_kvs=enc_kvs)
    loss = chunked_ce_loss(params, x[:, prefix:], tokens, cfg)
    total = loss + 0.01 * aux["load_balance"] + 0.001 * aux["router_z"]
    return total, {"ce": loss, **aux}


# number of unrolled head chunks for the CE loss (memory: per-device logits
# never exceed ~tokens/NC × V/model_shards × 4B)
CE_CHUNKS = 16


def chunked_ce_loss(params, x, tokens, cfg: ArchConfig):
    """Next-token CE without materializing the full (B, S, V) logits.

    Chunks run along the SEQUENCE axis (batch stays sharded over the data
    axes; slicing the flattened token dim would break the sharding) in an
    unrolled loop — roofline-exact, and XLA frees each chunk's logits
    before the next.
    """
    from repro.perf_flags import FLAGS
    B, S, D = x.shape
    x = ann(x, BATCH, None, None)        # gather S: chunks slice along S
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    xs = x[:, :-1]                       # (B, S-1, D)
    tg = tokens[:, 1:]
    n_tok = S - 1
    nc = min(FLAGS.ce_chunks, n_tok)
    pad = (-n_tok) % nc
    if pad:
        xs = jnp.pad(xs, [(0, 0), (0, pad), (0, 0)])
        tg = jnp.pad(tg, [(0, 0), (0, pad)])
    wts = None
    if pad:
        wts = jnp.concatenate([jnp.ones((n_tok,), jnp.float32),
                               jnp.zeros((pad,), jnp.float32)])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    csz = xs.shape[1] // nc

    def chunk_nll(xc, tc, wc):
        logits = jnp.einsum("bsd,dv->bsv", xc, head).astype(jnp.float32)
        logits = ann(logits, BATCH, None, "model")
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        if cfg.final_softcap:
            logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
        lp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(lp, tc[..., None], -1)[..., 0]
        if wc is not None:
            nll = nll * wc[None, :]
        return nll.sum()

    if cfg.remat:  # recompute chunk logits in backward: O(B·csz·V) live, once
        chunk_nll = jax.checkpoint(chunk_nll, prevent_cse=False)
    total = 0.0
    for c in range(nc):
        total = total + chunk_nll(
            xs[:, c * csz:(c + 1) * csz], tg[:, c * csz:(c + 1) * csz],
            None if wts is None else wts[c * csz:(c + 1) * csz])
    return total / (B * n_tok)


def _fixup_prefill_cache(caches, cfg: ArchConfig, S: int, pad_to: int | None,
                         lengths=None):
    """Convert full-length prefill KV to decode layout: windowed layers get
    ring-ordered last-``window`` entries; full layers optionally pad the S
    axis to ``pad_to`` for decode headroom.

    ``lengths``: optional (B,) per-sequence live lengths (including any
    VLM prefix) for tail-padded mixed-length batches — windowed rings are
    then aligned per sequence (positions past a sequence's length hold
    pad garbage; decode masks them via its per-sequence cache_len)."""
    out = {}
    for i, spec in enumerate(cfg.pattern):
        c = caches[f"p{i}"]
        if spec.kind != "attn":
            out[f"p{i}"] = c
            continue
        k, v = c["k"], c["v"]          # (n_super, B, S, K, hd)
        if spec.window is not None:
            # buffer = min(window, max(S, pad_to)): ring once past window,
            # padded headroom before that
            target = min(spec.window, max(S, pad_to or S))
            if lengths is not None:
                # ring slot j of a length-L sequence holds position
                # p_j = L-1 - ((L-1-j) mod target)  (the last `target`
                # positions in ring order); out-of-range slots clip to a
                # garbage row that decode's cache_len mask hides
                j = jnp.arange(target)
                last = lengths[:, None] - 1                 # (B, 1)
                src = jnp.clip(last - ((last - j[None]) % target), 0, S - 1)
                idx = src[None, :, :, None, None]           # (1,B,T,1,1)
                k = jnp.take_along_axis(k, idx, axis=2)
                v = jnp.take_along_axis(v, idx, axis=2)
            elif S > target:           # ring of exactly `window`
                s0 = (S - target) % target
                k = jnp.roll(k[:, :, -target:], s0, axis=2)
                v = jnp.roll(v[:, :, -target:], s0, axis=2)
            elif target > S:           # decode headroom below the window
                pad = [(0, 0), (0, 0), (0, target - S), (0, 0), (0, 0)]
                k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        elif pad_to and pad_to > k.shape[2]:
            pad = [(0, 0), (0, 0), (0, pad_to - k.shape[2]), (0, 0), (0, 0)]
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        out[f"p{i}"] = {"k": k, "v": v}
    return out


def prefill(params, batch, cfg: ArchConfig, pad_to: int | None = None):
    """Forward building caches; returns (last_logits, cache_pytree).

    ``batch["lengths"]`` (optional, (B,) int32): per-sequence real prompt
    lengths for tail-padded mixed-length batches.  Last logits are then
    taken at each sequence's own final token (not the pad tail) and the
    cache ``pos`` becomes a per-sequence vector, so decode continues each
    sequence at ITS length — pad rows beyond a sequence's length are
    masked by decode's per-sequence cache_len and progressively
    overwritten by decoded tokens.
    """
    tokens = batch["tokens"]
    lengths = batch.get("lengths")
    x = embed_tokens(params, tokens, cfg)
    enc_kvs = None
    extra = {}
    prefix = 0
    if cfg.encoder_layers:
        enc_out = run_encoder(params, batch["frames"], cfg)
        enc_kvs = encoder_cross_kv(params, enc_out, cfg)
        extra["enc_kvs"] = enc_kvs
    elif cfg.frontend_tokens:
        pre = batch["patches"].astype(cfg.activation_dtype()) \
            @ params["frontend_proj"]
        x = jnp.concatenate([pre, x], axis=1)
        prefix = pre.shape[1]
    eff = (None if lengths is None
           else (prefix + lengths).astype(jnp.int32))   # incl. VLM prefix
    x, caches, _ = run_stack(params, x, cfg, enc_kvs=enc_kvs,
                             collect_cache=True, lengths=eff)
    S = x.shape[1]
    if lengths is None:
        caches = _fixup_prefill_cache(caches, cfg, S, pad_to)
        logits = final_logits(params, x[:, -1:], cfg)
        pos = jnp.asarray(S, jnp.int32)
    else:
        caches = _fixup_prefill_cache(caches, cfg, S, pad_to, lengths=eff)
        x_last = jnp.take_along_axis(x, (eff - 1)[:, None, None], axis=1)
        logits = final_logits(params, x_last, cfg)
        pos = eff
    return logits[:, 0], {"layers": caches, **extra, "pos": pos}


def _stack_step(cfg, body, carry, xs):
    """Run ``body(carry, xs_i) -> (carry, y)`` over the super-block stack
    (unrolled <=4 for exact cost_analysis, ``lax.scan`` else), stacking
    the per-super-block outputs ``y`` — the shared dispatch of every
    decode/prefill step.  ``xs_i["layer"]`` is the super-block's index,
    a Python int when unrolled, so a body can address the stacked pools
    it carries."""
    xs = {**xs, "layer": np.arange(cfg.n_super, dtype=np.int32)}
    if cfg.n_super <= 4:
        ys = []
        for i in range(cfg.n_super):
            carry, y = body(carry, jax.tree.map(lambda a: a[i], xs))
            ys.append(y)
        return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)
    return jax.lax.scan(body, carry, xs)


def decode_step(params, cache, batch, cfg: ArchConfig):
    """One-token serve step. batch: {"tokens": (B, 1)}; cache from
    make_cache/prefill. Returns (logits (B, V), new_cache)."""
    tokens = batch["tokens"]
    x = embed_tokens(params, tokens, cfg)
    pos = cache["pos"]
    pattern = cfg.pattern
    enc_kvs = cache.get("enc_kvs")

    def body(x, xs):
        bp, layer_cache = xs["params"], xs["cache"]
        new_caches = {}
        for i, spec in enumerate(pattern):
            enc_kv = xs["enc"][f"p{i}"] if (spec.cross_attn and
                                            enc_kvs is not None) else None
            x, nc, _ = apply_block_decode(bp[f"p{i}"], x,
                                          layer_cache[f"p{i}"], pos, cfg,
                                          spec, enc_kv=enc_kv)
            new_caches[f"p{i}"] = nc
        return x, new_caches

    xs = {"params": params["blocks"], "cache": cache["layers"]}
    if enc_kvs is not None:
        xs["enc"] = enc_kvs
    x, new_layers = _stack_step(cfg, body, x, xs)
    logits = final_logits(params, x[:, -1:], cfg)
    new_cache = {**cache, "layers": new_layers, "pos": pos + 1}
    return logits[:, 0], new_cache


# ---------------------------------------------------------------------------
# cache construction (decode entry without a real prefill — dry-run path)

def cache_len_for(cfg: ArchConfig, spec: LayerSpec, seq_len: int) -> int:
    if spec.window is not None:
        return min(seq_len, spec.window)
    return seq_len


def make_cache(cfg: ArchConfig, batch: int, seq_len: int, enc_len: int = 0):
    """Zeroed cache pytree sized for ``seq_len`` context (ring-buffered to
    ``window`` for windowed layers)."""
    dt = cfg.activation_dtype()
    K, hd = cfg.n_kv_heads, cfg.hd
    layers = {}
    for i, spec in enumerate(cfg.pattern):
        n = cfg.n_super
        if spec.kind == "attn":
            S = cache_len_for(cfg, spec, seq_len)
            layers[f"p{i}"] = {
                "k": jnp.zeros((n, batch, S, K, hd), dt),
                "v": jnp.zeros((n, batch, S, K, hd), dt)}
        else:
            ch = cfg.d_inner + 2 * cfg.ssm_state
            layers[f"p{i}"] = {
                "conv": jnp.zeros((n, batch, cfg.conv_width - 1, ch), dt),
                "ssm": jnp.zeros((n, batch, cfg.ssm_heads, cfg.ssm_p,
                                  cfg.ssm_state), jnp.float32)}
    cache = {"layers": layers, "pos": jnp.asarray(seq_len - 1, jnp.int32)}
    if cfg.encoder_layers:
        enc_len = enc_len or cfg.frontend_tokens
        kvs = {}
        for i, spec in enumerate(cfg.pattern):
            if spec.cross_attn:
                kvs[f"p{i}"] = (jnp.zeros((cfg.n_super, batch, enc_len, K, hd), dt),
                                jnp.zeros((cfg.n_super, batch, enc_len, K, hd), dt))
        cache["enc_kvs"] = kvs
    return cache


# ---------------------------------------------------------------------------
# paged decode path (DESIGN.md §9): block-pool KV cache + per-slot SSM
# states, continuous-batching step functions.  Host-side block bookkeeping
# lives in repro.serve.paging; these are the pure device-side steps.


def make_paged_cache(cfg: ArchConfig, num_blocks: int, block_size: int,
                     max_batch: int, kv_dtype=None):
    """Zeroed paged cache: per attention pattern-position one stacked,
    lane-dense block pool (n_super, num_blocks, block_size, K*hd) for k
    and one for v, a row holding one token's K heads side by side; SSM
    layers keep per-slot recurrent states (their footprint is
    position-independent — nothing to page).  Block 0 is the sink
    (``serve.paging.SINK_BLOCK``).  The serving steps carry the pools
    through the layer scan and write only their new rows, and the paged
    kernel reads a layer of them as they lie (DESIGN.md §9).

    ``kv_dtype``: None/"native" stores KV in the activation dtype;
    "int8"/"fp8_e4m3"/"fp8_e5m2" store quantized rows plus per-(token,
    kv-head) f32 scale pools "k_scale"/"v_scale" (n_super, num_blocks,
    block_size, K) riding alongside (DESIGN.md §13)."""
    if cfg.encoder_layers:
        raise ValueError("paged decode does not support enc-dec archs "
                         "(cross-attention caches are per-request static)")
    from repro.kernels.quant import resolve_kv_dtype
    qdt = resolve_kv_dtype(kv_dtype)
    dt = cfg.activation_dtype()
    K, hd = cfg.n_kv_heads, cfg.hd
    layers = {}
    for i, spec in enumerate(cfg.pattern):
        n = cfg.n_super
        if spec.kind == "attn":
            rows = (n, num_blocks, block_size, K * hd)
            layers[f"p{i}"] = {"k": jnp.zeros(rows, qdt or dt),
                               "v": jnp.zeros(rows, qdt or dt)}
            if qdt is not None:
                layers[f"p{i}"]["k_scale"] = jnp.zeros(
                    (n, num_blocks, block_size, K), jnp.float32)
                layers[f"p{i}"]["v_scale"] = jnp.zeros(
                    (n, num_blocks, block_size, K), jnp.float32)
        else:
            ch = cfg.d_inner + 2 * cfg.ssm_state
            layers[f"p{i}"] = {
                "conv": jnp.zeros((n, max_batch, cfg.conv_width - 1, ch), dt),
                "ssm": jnp.zeros((n, max_batch, cfg.ssm_heads, cfg.ssm_p,
                                  cfg.ssm_state), jnp.float32)}
    cache = {"layers": layers}
    if any(spec.mlp == "moe" for spec in cfg.pattern):
        cache["counts"] = _zero_counts()
    return cache


def _zero_counts() -> dict:
    """A serving step's counts of its expert layers' work, written by the
    step beside the cache: rows the held experts received (summed over
    layers) and the busiest held expert's rows in any one layer."""
    return {"expert_rows": jnp.zeros((), jnp.int32),
            "expert_rows_max": jnp.zeros((), jnp.int32)}


def _split_paged(cfg: ArchConfig, layers):
    """The paged cache's layers as (pools, states): the attention
    positions' stacked KV pools and the SSM positions' stacked per-slot
    states.  The layer scan carries both and each step updates them in
    place: a decode step writes each SSM layer's lanes, a prefill chunk
    its one slot's rows (DESIGN.md §9, §16)."""
    pools = {f"p{i}": layers[f"p{i}"] for i, spec in enumerate(cfg.pattern)
             if spec.kind == "attn"}
    states = {name: c for name, c in layers.items() if name not in pools}
    return pools, states


def paged_swap_out(cache, slot: int, block_ids) -> dict:
    """Copy decode lane ``slot``'s live state out of the paged cache to
    host memory (preemption, DESIGN.md §14): for every attention layer
    the lane's physical block rows (codes + quant scales when present),
    for every SSM layer the lane's conv + recurrent state rows.  Returns
    a flat ``{"p<i>.<key>": np.ndarray}`` dict — a bit-exact snapshot
    (same dtypes, no recompute) that ``paged_swap_in`` restores under
    possibly different block ids / a different slot."""
    ids = np.asarray(list(block_ids), np.int32)
    out = {}
    for name, layer in cache["layers"].items():
        if "k" in layer:                       # attn: block-pool rows
            for key in layer:                  # k/v (+ k_scale/v_scale)
                out[f"{name}.{key}"] = np.array(layer[key][:, ids])
        else:                                  # ssm: per-slot state rows
            out[f"{name}.conv"] = np.array(layer["conv"][:, slot])
            out[f"{name}.ssm"] = np.array(layer["ssm"][:, slot])
    return out


def paged_swap_in(cache, slot: int, block_ids, payload: dict):
    """Inverse of ``paged_swap_out``: write the copied rows back into the
    pools at fresh ``block_ids`` and the (possibly different) lane
    ``slot``.  Pure eager updates — the round trip is bit-exact, so a
    preempted-and-restored request emits identical greedy tokens."""
    ids = jnp.asarray(np.asarray(list(block_ids), np.int32))
    new_layers = {}
    for name, layer in cache["layers"].items():
        if "k" in layer:
            new_layers[name] = {
                key: layer[key].at[:, ids].set(
                    jnp.asarray(payload[f"{name}.{key}"], layer[key].dtype))
                for key in layer}
        else:
            new_layers[name] = {
                key: layer[key].at[:, slot].set(
                    jnp.asarray(payload[f"{name}.{key}"], layer[key].dtype))
                for key in ("conv", "ssm")}
    return {**cache, "layers": new_layers}


def decode_step_paged(params, cache, batch, cfg: ArchConfig):
    """One continuous-batching decode step.

    batch: tokens (B, 1); block_tables (B, P) int32 (sink-filled for
    inactive lanes); pos (B,) int32 — the incoming token's absolute
    position per lane (0 for inactive lanes, whose writes land in the
    sink block); active (B,) bool — lanes decoding this step (inactive
    lanes' SSM states are preserved).  Returns (logits (B, V), new_cache).
    The KV pools and SSM states ride the layer scan's carry: each layer
    writes its B new KV rows and its lanes' states in place, and the
    kernel reads the layer where it lies.  A cache with expert layers
    carries the step's ``counts`` out beside them.
    """
    tokens, tables, pos = batch["tokens"], batch["block_tables"], batch["pos"]
    active = batch["active"]
    x = embed_tokens(params, tokens, cfg)
    pattern = cfg.pattern

    def body(carry, xs):
        x, pools, states, counts = carry
        pools, states = dict(pools), dict(states)
        bp, layer = xs["params"], xs["layer"]
        for i, spec in enumerate(pattern):
            name = f"p{i}"
            if name in pools:
                x, pools[name], aux = apply_block_decode(
                    bp[name], x, pools[name], pos, cfg, spec,
                    block_tables=tables, active=active, layer=layer)
            else:
                x, states[name], aux = apply_block_decode(
                    bp[name], x, states[name], pos, cfg, spec,
                    active=active, layer=layer)
            counts = _add_counts(counts, aux)
        return (x, pools, states, counts), None

    pools, states = _split_paged(cfg, cache["layers"])
    counts = _zero_counts() if "counts" in cache else {}
    (x, pools, states, counts), _ = _stack_step(
        cfg, body, (x, pools, states, counts), {"params": params["blocks"]})
    logits = final_logits(params, x[:, -1:], cfg)
    new = {**cache, "layers": {**pools, **states}}
    if counts:
        new["counts"] = counts
    return logits[:, 0], new


def _apply_block_prefill_paged(p, x, layer_cache, cfg, spec, *, tables,
                               start, length, slot, positions, layer):
    """One block of a paged prefill chunk.  x: (1, C, D).  An attention
    block writes the chunk's K/V rows into layer ``layer`` of the stacked
    pools (``layer_cache``) through the (1, P) block table (pad rows ->
    sink) and attends against its own P pages gathered back; an SSM block
    reads the slot's states from layer ``layer`` of the stacked slot
    states and writes its new ones back there, one slot's rows.  Returns
    (x, new_layer_cache, aux), ``aux`` an expert layer's."""
    C = x.shape[1]
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    if spec.kind == "attn":
        pools = layer_cache
        bs = pools["k"].shape[2]
        P = tables.shape[1]
        q, k, v = attn_project_qkv(p["attn"], h, cfg)
        if cfg.position_embedding == "rope":
            cos, sin = rope_freqs(positions, cfg.hd, cfg.rope_theta)
            q = apply_rope(q, cos[None], sin[None])
            k = apply_rope(k, cos[None], sin[None])
        # pad rows write garbage into the sink block's row 0, masked out
        # by kv_len
        real = jnp.arange(C) < length
        page = tables[0, jnp.clip(positions // bs, 0, P - 1)]
        page = jnp.where(real, page, 0)
        off = jnp.where(real, positions % bs, 0)
        pools = paged_append(pools, layer, page, off, k[0], v[0])
        # attend to the logical context, the chunk's own rows included
        ctx_k, ctx_v = paged_gather(pools, layer, tables[0], cfg.n_kv_heads)
        h = paged_context_attention(q, ctx_k, ctx_v, q_offset=start,
                                    kv_len=start + length,
                                    window=spec.window,
                                    softcap=cfg.attn_softcap,
                                    scale=cfg.attention_multiplier)
        h = jnp.einsum("bshk,hkd->bsd", h, p["attn"]["wo"])
        new_cache = pools
    else:
        conv_all, ssm_all = layer_cache["conv"], layer_cache["ssm"]
        at = (layer, slot, 0, 0)
        conv0 = jax.lax.dynamic_slice(conv_all, at,
                                      (1, 1) + conv_all.shape[2:])[0]
        ssm0 = jax.lax.dynamic_slice(ssm_all, at + (0,),
                                     (1, 1) + ssm_all.shape[2:])[0]
        fresh = start == 0           # first chunk starts from zero state
        conv0 = jnp.where(fresh, jnp.zeros_like(conv0), conv0)
        ssm0 = jnp.where(fresh, jnp.zeros_like(ssm0), ssm0)
        h, (nconv, nssm) = mamba_block(p["ssm"], h, cfg, conv_state=conv0,
                                       ssm_state=ssm0, valid_len=length)
        new_cache = {
            "conv": jax.lax.dynamic_update_slice(
                conv_all, nconv[None].astype(conv_all.dtype), at),
            "ssm": jax.lax.dynamic_update_slice(
                ssm_all, nssm[None].astype(ssm_all.dtype), at + (0,))}
    if cfg.sandwich_norm:
        h = rmsnorm(h, p["ln1_post"], cfg.norm_eps)
    x = x + _res(h, cfg)
    aux = {}
    if spec.mlp != "none":
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        if spec.mlp == "moe":
            h, aux = moe_block(p["moe"], h, cfg, valid_len=length)
        else:
            h = mlp_block(p["mlp"], h, spec.mlp)
        if cfg.sandwich_norm:
            h = rmsnorm(h, p["ln2_post"], cfg.norm_eps)
        x = x + _res(h, cfg)
    return x, new_cache, aux


def prefill_chunk_paged(params, cache, batch, cfg: ArchConfig):
    """One prompt chunk of a paged prefill (continuous batching admits
    long prompts chunk by chunk so decode lanes never stall behind them).

    batch: tokens (1, C) (tail-padded); block_tables (1, P) int32 for the
    admitted slot; start (scalar) absolute position of tokens[:, 0];
    length (scalar) real tokens in this chunk; slot (scalar) the decode
    lane (SSM state row).  Returns (last_real_token_logits (1, V),
    new_cache); the KV pools and SSM states ride the layer scan's carry,
    as in ``decode_step_paged``.
    """
    tokens, tables = batch["tokens"], batch["block_tables"]
    start, length, slot = batch["start"], batch["length"], batch["slot"]
    C = tokens.shape[1]
    x = embed_tokens(params, tokens, cfg)
    positions = start + jnp.arange(C)
    pattern = cfg.pattern

    def body(carry, xs):
        x, pools, states, counts = carry
        pools, states = dict(pools), dict(states)
        bp, layer = xs["params"], xs["layer"]
        for i, spec in enumerate(pattern):
            name = f"p{i}"
            held = pools if name in pools else states
            x, held[name], aux = _apply_block_prefill_paged(
                bp[name], x, held[name], cfg, spec, tables=tables,
                start=start, length=length, slot=slot, positions=positions,
                layer=layer)
            counts = _add_counts(counts, aux)
        return (x, pools, states, counts), None

    pools, states = _split_paged(cfg, cache["layers"])
    counts = _zero_counts() if "counts" in cache else {}
    (x, pools, states, counts), _ = _stack_step(
        cfg, body, (x, pools, states, counts), {"params": params["blocks"]})
    x_last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
    logits = final_logits(params, x_last, cfg)
    new = {**cache, "layers": {**pools, **states}}
    if counts:
        new["counts"] = counts
    return logits[:, 0], new
