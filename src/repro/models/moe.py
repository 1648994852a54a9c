"""Mixture-of-Experts layer: token-choice top-k routing, on one of two
paths, chosen by what the layer can observe (DESIGN.md §16).

* Drop-free grouped path (``moe_grouped``), where the layer holds a share
  of the experts (``ArchConfig.experts_held``: one chip of an expert-
  parallel deployment) or runs on one chip.  The router scores every
  expert in f32 and each token takes its top-k; the rows routed to the
  held experts are sorted by expert, each expert's rows padded to a
  whole tile, and one grouped matmul (``kernels/expert_gmm.py`` on a TPU,
  its jnp oracle elsewhere) computes each row's own expert.  No row is
  dropped; rows routed to experts this chip does not hold contribute
  nothing here (that is the absent chips' part), and nothing stands in
  for them or for their exchange.

* Capacity path (``moe_capacity``), under a mesh of several devices:
  GShard-style static expert capacity with GROUPED dispatch.  The
  scatter/gather dispatch runs *locally* under ``shard_map`` over the
  data axes (each data shard slots its own tokens into its local
  (B_loc, E, C, D) buffer — no partitioner involvement, which otherwise
  replicates batched scatters), while the expert FFN einsum runs under
  GSPMD with experts sharded over the "model" axis — the group->expert
  resharding is the all-to-all of expert parallelism.  Capacity is per
  group (= batch row): C = ceil(S·k/E · capacity_factor); overflow tokens
  are dropped (contribute zero), exactly like GShard/Switch.

Both add the always-on shared expert, once.  Aux losses: Switch
load-balance + router z-loss.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist.annotate import BATCH, ann, _mesh_axes


def _router_aux(logits, idx, E):
    """Switch load-balance and router z-loss over every expert."""
    probs = jax.nn.softmax(logits, -1)
    f = jnp.mean(jax.nn.one_hot(idx, E, dtype=jnp.float32),
                 axis=tuple(range(idx.ndim)))
    pbar = probs.reshape(-1, E).mean(0)
    return {"load_balance": E * jnp.sum(f * pbar),
            "router_z": jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)}


def moe_router(p, x, cfg):
    """x: (B, S, D) -> weights (B,S,k), experts (B,S,k), aux."""
    from repro.perf_flags import FLAGS
    if FLAGS.router_no_f32_copy:
        # §Perf: f32 ACCUMULATION without materializing an f32 copy of x
        # (the copy doubles the reshard bytes of the sequence-parallel x)
        logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(x.dtype),
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                            p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, -1)
    w, idx = jax.lax.top_k(probs, cfg.top_k)
    w = w / jnp.clip(w.sum(-1, keepdims=True), 1e-9)
    return w.astype(x.dtype), idx, _router_aux(logits, idx, cfg.n_experts)


# ---------------------------------------------------------------------------
# local (per data-shard) dispatch/combine bodies


def _slots(flat_e, E, C):
    """Position of each (token, choice) within its expert's capacity."""
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)   # (B, S*k, E)
    pos_in_e = jnp.cumsum(onehot, axis=1) - 1
    slot = jnp.take_along_axis(pos_in_e, flat_e[..., None], 2)[..., 0]
    keep = slot < C
    return jnp.where(keep, slot, 0), keep


def _dispatch_local(x, flat_e, flat_t, E, C):
    """x: (B, S, D) local. Returns buf (B, E, C, D), s_idx, keep."""
    B = x.shape[0]
    Sk = flat_e.shape[1]
    s_idx, keep = _slots(flat_e, E, C)
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, Sk))
    e_idx = jnp.where(keep, flat_e, 0)
    xt = jnp.take_along_axis(x, flat_t[..., None], axis=1)   # (B, S*k, D)
    contrib = jnp.where(keep[..., None], xt, 0)
    buf = jnp.zeros((B, E, C, x.shape[-1]), x.dtype)
    buf = buf.at[bidx, e_idx, s_idx].add(contrib, mode="drop")
    return buf, s_idx, keep


def _combine_local(y, flat_e, flat_t, flat_w, s_idx, keep, S):
    """y: (B, E, C, D) local -> (B, S, D)."""
    B, E, C, D = y.shape
    Sk = flat_e.shape[1]
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, Sk))
    e_idx = jnp.where(keep, flat_e, 0)
    gathered = y[bidx, e_idx, s_idx]
    gathered = jnp.where(keep[..., None], gathered, 0)
    out = jnp.zeros((B, S, D), y.dtype)
    out = out.at[bidx, flat_t].add(gathered * flat_w[..., None].astype(y.dtype))
    return out


def _data_shard_map(f, n_in, n_out, batch_dim: int = 0, batch_size=None):
    """Run f under shard_map over the data axes (manual) with "model" left
    auto; identity passthrough when no mesh is active (CPU tests), when
    the batch dim does not divide the data axes (e.g. batch-1 long-context
    decode — the local code is then simply global), or inside an enclosing
    fully-manual region (the pipeline stage body, DESIGN.md §10 — the
    batch axes are already per-device there, so f's local body is exactly
    what should run)."""
    from repro.dist.annotate import annotations_suppressed
    if annotations_suppressed():
        return f
    axes, sizes = _mesh_axes()
    dp = tuple(a for a in ("pod", "data") if a in axes)
    if not dp:
        return f
    n = 1
    for a in dp:
        n *= sizes[a]
    if batch_size is not None and batch_size % n != 0:
        return f
    spec_in = tuple(P(dp) for _ in range(n_in))
    spec_out = tuple(P(dp) for _ in range(n_out)) if n_out > 1 else P(dp)
    return jax.shard_map(f, in_specs=spec_in, out_specs=spec_out,
                         axis_names=set(dp), check_vma=False)


def _dispatch_local_kloop(x, idx, k, E, C):
    """k compact scatters: buf from x (B,S,D) without (B,S*k,D).

    idx: (B, S, k). Returns buf (B,E,C,D), s_idx (B,S,k), keep (B,S,k).
    """
    B, S, D = x.shape
    flat_e = idx.reshape(B, S * k)
    s_flat, keep_flat = _slots(flat_e, E, C)
    s_idx = s_flat.reshape(B, S, k)
    keep = keep_flat.reshape(B, S, k)
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, S))
    buf = jnp.zeros((B, E, C, D), x.dtype)
    for j in range(k):
        e_j = jnp.where(keep[..., j], idx[..., j], 0)
        s_j = jnp.where(keep[..., j], s_idx[..., j], 0)
        contrib = jnp.where(keep[..., j, None], x, 0)
        buf = buf.at[bidx, e_j, s_j].add(contrib, mode="drop")
    return buf, s_idx, keep


def _combine_local_kloop(y, idx, w, s_idx, keep):
    """k compact gathers from y (B,E,C,D) -> (B,S,D)."""
    B, E, C, D = y.shape
    S, k = idx.shape[1], idx.shape[2]
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, S))
    out = jnp.zeros((B, S, D), y.dtype)
    for j in range(k):
        e_j = jnp.where(keep[..., j], idx[..., j], 0)
        s_j = jnp.where(keep[..., j], s_idx[..., j], 0)
        g = y[bidx, e_j, s_j]                       # (B, S, D)
        g = jnp.where(keep[..., j, None], g, 0)
        out = out + g * w[..., j, None].astype(y.dtype)
    return out


def moe_block(p, x, cfg, mlp_kind="swiglu", valid_len=None):
    """x: (B, S, D) -> (B, S, D), aux: the drop-free grouped path where
    the layer holds a share of the experts or runs on one chip, the
    capacity path under a mesh of several devices.  ``valid_len``: only
    the first ``valid_len`` rows of each sequence are real (a chunked
    prefill's padded tail is routed nowhere on the grouped path)."""
    if grouped_path(cfg):
        return moe_grouped(p, x, cfg, valid_len=valid_len)
    return moe_capacity(p, x, cfg, mlp_kind)


def grouped_path(cfg) -> bool:
    """Whether the expert layer runs drop-free: it holds a share of the
    experts, or no mesh spreads it over several devices."""
    if cfg.experts_held:
        return True
    _, sizes = _mesh_axes()
    return all(n == 1 for n in sizes.values())


def _shared_expert(p, x):
    hs = jax.nn.silu(x @ p["shared_wg"]) * (x @ p["shared_wu"])
    hs = ann(hs, BATCH, None, "model")
    return hs @ p["shared_wd"]


def rows_per_tile(tokens: int, top_k: int, n_experts: int) -> int:
    """Row tile of the grouped matmul: the expected rows of one expert
    (tokens x top_k / experts) rounded up to a power of two, 16 to 128 (a
    tile that an expert's rows fill, and never one past the MXU's
    height)."""
    want = -(-tokens * top_k // n_experts)
    return min(128, max(16, 1 << (want - 1).bit_length()))


def grouped_swiglu(x, wg, wu, wd, tile_group, n_tiles, block_rows):
    """Each row tile of ``x`` through its expert's SwiGLU: the
    ``expert_gmm`` kernel on a TPU (its backward from the oracle's), the
    jnp oracle elsewhere (interpret-mode Pallas runs its grid in
    Python)."""
    from repro.kernels.ref import expert_gmm_ref
    ref = functools.partial(expert_gmm_ref, block_rows=block_rows)
    if jax.default_backend() != "tpu":
        return ref(x, wg, wu, wd, tile_group, n_tiles)
    from repro.kernels.ops import expert_gmm

    @jax.custom_vjp
    def gmm(x, wg, wu, wd):
        return expert_gmm(x, wg, wu, wd, tile_group, n_tiles,
                          block_rows=block_rows)

    def fwd(x, wg, wu, wd):
        return gmm(x, wg, wu, wd), (x, wg, wu, wd)

    def bwd(res, g):
        _, vjp = jax.vjp(lambda *a: ref(*a, tile_group, n_tiles), *res)
        return vjp(g)

    gmm.defvjp(fwd, bwd)
    return gmm(x, wg, wu, wd)


def moe_grouped(p, x, cfg, valid_len=None):
    """Drop-free expert layer over the held experts ``[expert_offset,
    expert_offset + n_held)``.  x: (B, S, D) -> (B, S, D), aux.

    1. router logits in f32 over all ``n_experts``; 2. each token's top-k;
    3. gates: softmax over the k chosen logits; 4. each row routed to a
    held expert through that expert's SwiGLU (one grouped matmul over
    rows sorted by expert, each expert's rows padded to a whole tile);
    5. rows routed to other experts add nothing here; 6. the shared
    expert, for every token, once.  ``aux`` carries, beside the router
    losses, ``rows`` (rows the held experts received) and ``rows_max``
    (the busiest held expert's)."""
    B, S, D = x.shape
    T, k, E = B * S, cfg.top_k, cfg.n_experts
    Eh, lo = cfg.n_held, cfg.expert_offset
    xf = x.reshape(T, D)
    logits = jnp.dot(xf.astype(jnp.float32),
                     p["router"].astype(jnp.float32))            # (T, E)
    top, idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top, -1)                              # (T, k)
    aux = _router_aux(logits, idx, E)

    tm = rows_per_tile(T, k, E)
    # worst case: every token's choices among the held experts, plus each
    # expert's padding to a whole tile
    M = -(-(T * min(k, Eh) + Eh * (tm - 1)) // tm) * tm
    e = (idx - lo).reshape(T * k)
    held = (e >= 0) & (e < Eh)
    if valid_len is not None:
        real = jnp.arange(S)[None, :] < jnp.asarray(valid_len).reshape(-1, 1)
        held &= jnp.repeat(jnp.broadcast_to(real, (B, S)).reshape(T), k)
    e = jnp.where(held, e, Eh)                     # Eh: not held here
    onehot = jax.nn.one_hot(e, Eh + 1, dtype=jnp.int32)[:, :Eh]
    counts = onehot.sum(0)                                       # (Eh,)
    padded = -(-counts // tm) * tm
    ends = jnp.cumsum(padded)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, 0),
                               jnp.minimum(e, Eh - 1)[:, None], 1)[:, 0] - 1
    pos = jnp.where(held, (ends - padded)[jnp.minimum(e, Eh - 1)] + rank, M)
    token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    # row r of the sorted buffer holds token row_token[r] (T: a zero pad)
    row_token = jnp.full((M,), T, jnp.int32).at[pos].set(token, mode="drop")
    xs = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)])[row_token]
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(M // tm) * tm, side="right"),
        Eh - 1).astype(jnp.int32)
    ys = grouped_swiglu(xs, p["wg"], p["wu"], p["wd"], tile_group,
                        ends[-1] // tm, tm)                      # (M, D)
    picked = ys[jnp.minimum(pos, M - 1)].astype(jnp.float32)     # (T*k, D)
    w = jnp.where(held, gates.reshape(T * k), 0.0)
    out = jnp.where(held[:, None], picked, 0.0) * w[:, None]
    out = out.reshape(T, k, D).sum(1).astype(x.dtype).reshape(B, S, D)
    if cfg.shared_expert:
        out = out + _shared_expert(p, x)
    aux["rows"] = counts.sum()
    aux["rows_max"] = counts.max()
    return out, aux


def moe_capacity(p, x, cfg, mlp_kind="swiglu"):
    """x: (B, S, D) -> (B, S, D), aux. Grouped dispatch: group == batch
    row, a static capacity per expert, overflow dropped."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = max(int(np.ceil(S * k / E * cfg.capacity_factor)), 1)

    from repro.perf_flags import FLAGS
    if FLAGS.moe_gather_once:
        # §Perf: gather the sequence-parallel residual ONCE, compact and
        # bf16, before the S*k-expanded dispatch tensors exist
        x = ann(x, BATCH, None, None)
    w, idx, aux = moe_router(p, x, cfg)            # (B,S,k)

    if FLAGS.moe_k_loop:
        disp = _data_shard_map(
            lambda xx, ii: _dispatch_local_kloop(xx, ii, k, E, C), 2, 3,
            batch_size=B)
        buf, s_idx, keep = disp(x, idx)
    else:
        flat_e = idx.reshape(B, S * k)
        flat_w = w.reshape(B, S * k)
        flat_t = jnp.tile(jnp.repeat(jnp.arange(S), k)[None], (B, 1))
        disp = _data_shard_map(
            lambda xx, fe, ft: _dispatch_local(xx, fe, ft, E, C), 3, 3,
            batch_size=B)
        buf, s_idx, keep = disp(x, flat_e, flat_t)
    # batch over data, experts over model: this reshard is the all-to-all
    buf = ann(buf, BATCH, "model", None, None)

    if mlp_kind in ("swiglu", "geglu"):
        act = jax.nn.silu if mlp_kind == "swiglu" else (
            lambda a: jax.nn.gelu(a, approximate=True))
        h = act(jnp.einsum("becd,edf->becf", buf, p["wg"])) \
            * jnp.einsum("becd,edf->becf", buf, p["wu"])
        y = jnp.einsum("becf,efd->becd", h, p["wd"])
    else:
        h = jax.nn.gelu(jnp.einsum("becd,edf->becf", buf, p["wu"]),
                        approximate=True)
        y = jnp.einsum("becf,efd->becd", h, p["wd"])
    y = ann(y, BATCH, "model", None, None)

    if FLAGS.moe_k_loop:
        comb = _data_shard_map(
            lambda yy, ii, ww, si, kp: _combine_local_kloop(yy, ii, ww, si,
                                                            kp), 5, 1,
            batch_size=B)
        out = comb(y, idx, w, s_idx, keep)
    else:
        comb = _data_shard_map(
            lambda yy, fe, ft, fw, si, kp: _combine_local(yy, fe, ft, fw,
                                                          si, kp, S), 6, 1,
            batch_size=B)
        out = comb(y, flat_e, flat_t, flat_w, s_idx, keep)
    out = ann(out, BATCH, None, None)

    if cfg.shared_expert:
        out = out + _shared_expert(p, x)
    return out, aux
