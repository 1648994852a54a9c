"""Mamba-2 (SSD — state-space duality, arXiv:2405.21060) in pure JAX.

Training/prefill uses the chunked SSD algorithm: quadratic *within* a chunk
(MXU-friendly einsums over chunk length Q) and a sequential ``lax.scan``
over chunks carrying the (H, P, N) state — the TPU-native mapping of the
paper's "chunk-parallel" GPU kernel.  Decode is the O(1) recurrent update.

Layout: d_inner = expand * d_model, split into H heads of dim P;
B/C are shared across heads (n_groups = 1) with state size N.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.annotate import BATCH, ann, ann_first_fit

from .layers import rmsnorm


def _split_proj(p, x, cfg):
    """in_proj -> (z, xh, Bm, Cm, dt) with conv over (xh|B|C)."""
    di, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    zxbcdt = x @ p["in_proj"]                       # (B, T, 2di+2N+H)
    z, xBC, dt = jnp.split(zxbcdt, [di, di + di + 2 * N], axis=-1)
    return z, xBC, dt


def _causal_conv(xBC, weight, bias, prev=None, valid_len=None):
    """Depthwise causal conv, width W.  xBC: (B, T, Ch); weight: (W, Ch).

    ``prev``: (B, W-1, Ch) history for decode; returns (out, new_prev).
    ``valid_len``: only the first ``valid_len`` tokens are real (chunked
    prefill pads the tail) — the carried history then ends at the last
    REAL token, not the padding.
    """
    W = weight.shape[0]
    if prev is None:
        prev = jnp.zeros(xBC.shape[:1] + (W - 1,) + xBC.shape[2:], xBC.dtype)
    xpad = jnp.concatenate([prev, xBC], axis=1)     # (B, T+W-1, Ch)
    out = sum(xpad[:, i:i + xBC.shape[1]] * weight[i] for i in range(W))
    out = jax.nn.silu(out + bias)
    if W <= 1:
        new_prev = prev
    elif valid_len is None:
        new_prev = xpad[:, -(W - 1):]
    else:
        # real tokens occupy xpad[:, W-1 : W-1+valid_len); the last W-1 of
        # them start at index valid_len (scalar, or (B,) for per-sequence
        # tail-padded batches)
        vl = jnp.asarray(valid_len)
        if vl.ndim == 0:
            new_prev = jax.lax.dynamic_slice_in_dim(xpad, vl, W - 1, axis=1)
        else:
            idx = vl[:, None] + jnp.arange(W - 1)[None]       # (B, W-1)
            new_prev = jnp.take_along_axis(xpad, idx[:, :, None], axis=1)
    return out, new_prev


def ssd_chunked(xh, Bm, Cm, dt, A_log, D, chunk, init_state=None):
    """Chunked SSD scan.

    xh: (B, T, H, P); Bm, Cm: (B, T, N); dt: (B, T, H) (post-softplus);
    A_log: (H,); ``init_state``: optional (B, H, P, N) carry from a
    previous chunk call (paged/chunked prefill — resumes mid-sequence).
    Returns y: (B, T, H, P) and final state (B, H, P, N).
    """
    Bsz, T, H, P = xh.shape
    N = Bm.shape[-1]
    Q = min(chunk, T)
    T0 = T
    if T % Q:  # pad tail with dt=0 tokens: decay 1, zero contribution
        pad = Q - T % Q
        padT = lambda a: jnp.pad(a, [(0, 0), (0, pad)] +
                                 [(0, 0)] * (a.ndim - 2))
        xh, Bm, Cm, dt = padT(xh), padT(Bm), padT(Cm), padT(dt)
        T = T + pad
    nc = T // Q

    f32 = jnp.float32
    A = -jnp.exp(A_log.astype(f32))                 # (H,) negative
    dt = dt.astype(f32)
    dA = dt * A                                     # (B, T, H) log-decay
    # reshape into chunks
    xh_c = xh.reshape(Bsz, nc, Q, H, P).astype(f32)
    B_c = Bm.reshape(Bsz, nc, Q, N).astype(f32)
    C_c = Cm.reshape(Bsz, nc, Q, N).astype(f32)
    dA_c = dA.reshape(Bsz, nc, Q, H)
    dt_c = dt.reshape(Bsz, nc, Q, H)

    cum = jnp.cumsum(dA_c, axis=2)                  # (B, nc, Q, H)
    total = cum[:, :, -1]                           # (B, nc, H)

    # ---- intra-chunk (quadratic in Q, the MXU part)
    # shard the big (B,nc,Q,Q,H) tensors: SSD heads over "model" when they
    # divide, else chunk-parallel over "model" (both are TPU-natural)
    def shard5(t):
        return ann_first_fit(t, (BATCH, None, None, None, "model"),
                             (BATCH, "model", None, None, None),
                             (BATCH, None, None, None, None))

    # L[q, s] = exp(cum_q - cum_s) for s <= q else 0
    diff = shard5(cum[:, :, :, None, :] - cum[:, :, None, :, :])
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.where(mask[None, None, :, :, None], jnp.exp(diff), 0.0)
    # scores[q,s] = (C_q . B_s) * L[q,s] * dt_s
    cb = jnp.einsum("bcqn,bcsn->bcqs", C_c, B_c)
    scores = shard5(cb[..., None] * L * dt_c[:, :, None, :, :])
    y_intra = jnp.einsum("bcqsh,bcshp->bcqhp", scores, xh_c)

    # ---- inter-chunk state carry (sequential scan over chunks)
    # state contribution of chunk: sum_s exp(total - cum_s) * dt_s * B_s x_s
    decay_to_end = jnp.exp(total[:, :, None] - cum)         # (B,nc,Q,H)
    dBx = jnp.einsum("bcqh,bcqn,bcqhp->bchpn",
                     decay_to_end * dt_c, B_c, xh_c)        # (B,nc,H,P,N)
    chunk_decay = jnp.exp(total)                            # (B,nc,H)

    def step(state, inp):
        dBx_i, dec_i = inp                                  # (B,H,P,N),(B,H)
        new = state * dec_i[:, :, None, None] + dBx_i
        return new, state                                    # emit PREV state

    init = (jnp.zeros((Bsz, H, P, N), f32) if init_state is None
            else init_state.astype(f32))
    final, prev_states = jax.lax.scan(
        step, init,
        (jnp.moveaxis(dBx, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)           # (B,nc,H,P,N)

    # y_inter[q] = exp(cum_q) * C_q . state_prev
    y_inter = jnp.einsum("bcqh,bcqn,bchpn->bcqhp",
                         jnp.exp(cum), C_c, prev_states)

    y = (y_intra + y_inter).reshape(Bsz, T, H, P)
    y = y + D.astype(f32)[None, None, :, None] * xh.astype(f32)
    return y[:, :T0].astype(xh.dtype), final


def mamba_block(p, x, cfg, conv_state=None, ssm_state=None, decode=False,
                valid_len=None):
    """Full mamba2 block. x: (B, T, D).

    Training/prefill: decode=False, returns (out, (conv_state, ssm_state)).
    With ``cfg.ssm_gated_norm`` the gated output goes through Mamba-2's
    RMSNorm (weight ``p["norm"]``) before ``out_proj``.
    Decode: T == 1 with states provided; O(1) update.
    Chunked prefill: decode=False with states = the previous chunk's carry
    and ``valid_len`` = real tokens in this (possibly tail-padded) chunk —
    padded tokens get dt=0 (decay 1, zero contribution) so they cannot
    perturb the carried state, and the conv history ends at the last real
    token.
    """
    Bsz, T, Dm = x.shape
    di, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    P = cfg.ssm_p

    z, xBC, dt = _split_proj(p, x, cfg)
    z = ann(z, BATCH, None, "model")
    xBC = ann(xBC, BATCH, None, "model")
    xBC, new_conv = _causal_conv(xBC, p["conv_w"], p["conv_b"], conv_state,
                                 valid_len=valid_len)
    xh, Bm, Cm = jnp.split(xBC, [di, di + N], axis=-1)
    xh = ann(xh.reshape(Bsz, T, H, P), BATCH, None, "model", None)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    if valid_len is not None and not decode:
        vl = jnp.asarray(valid_len).reshape(-1, 1, 1)   # scalar or (B,)
        dt = jnp.where(jnp.arange(T)[None, :, None] < vl, dt, 0.0)

    if not decode:
        y, final = ssd_chunked(xh, Bm, Cm, dt, p["A_log"], p["D"],
                               cfg.ssm_chunk, init_state=ssm_state)
    else:
        # recurrent: state (B, H, P, N)
        f32 = jnp.float32
        A = -jnp.exp(p["A_log"].astype(f32))
        dA = jnp.exp(dt[:, 0] * A)                         # (B, H)
        dBx = jnp.einsum("bh,bn,bhp->bhpn", dt[:, 0],
                         Bm[:, 0].astype(f32), xh[:, 0].astype(f32))
        final = ssm_state * dA[:, :, None, None] + dBx
        y = jnp.einsum("bn,bhpn->bhp", Cm[:, 0].astype(f32), final)
        y = y + p["D"].astype(f32)[None, :, None] * xh[:, 0].astype(f32)
        y = y[:, None].astype(x.dtype)                     # (B, 1, H, P)

    y = y.reshape(Bsz, T, di)
    if cfg.ssm_gated_norm:
        # Mamba-2's gated RMSNorm over all d_inner channels (n_groups 1),
        # in f32: w * rmsnorm(y * silu(z))
        f32 = jnp.float32
        y = rmsnorm(y.astype(f32) * jax.nn.silu(z.astype(f32)), p["norm"],
                    cfg.norm_eps).astype(x.dtype)
    else:
        y = y * jax.nn.silu(z)                             # gated
    out = y @ p["out_proj"]
    return out, (new_conv, final)


def ssd_reference(xh, Bm, Cm, dt, A_log, D):
    """O(T) sequential oracle for tests: token-by-token recurrence."""
    Bsz, T, H, P = xh.shape
    N = Bm.shape[-1]
    f32 = jnp.float32
    A = -jnp.exp(A_log.astype(f32))
    xh, Bm, Cm, dt = (a.astype(f32) for a in (xh, Bm, Cm, dt))

    def step(state, inp):
        x_t, B_t, C_t, dt_t = inp
        dA = jnp.exp(dt_t * A)                              # (B,H)
        state = state * dA[:, :, None, None] + jnp.einsum(
            "bh,bn,bhp->bhpn", dt_t, B_t, x_t)
        y = jnp.einsum("bn,bhpn->bhp", C_t, state)
        return state, y

    init = jnp.zeros((Bsz, H, P, N), f32)
    _, ys = jax.lax.scan(step, init,
                         (jnp.moveaxis(xh, 1, 0), jnp.moveaxis(Bm, 1, 0),
                          jnp.moveaxis(Cm, 1, 0), jnp.moveaxis(dt, 1, 0)))
    y = jnp.moveaxis(ys, 0, 1)                              # (B,T,H,P)
    return y + D.astype(f32)[None, None, :, None] * xh
