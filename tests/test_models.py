"""Model-layer correctness: SSD vs sequential oracle, decode vs full
forward, windowed attention, GQA vs explicit reference, MoE dispatch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import get_model, reduced
from repro.models.layers import decode_attention, gqa_attention
from repro.models.ssm import ssd_chunked, ssd_reference

KEY = jax.random.PRNGKey(7)


# ---------------------------------------------------------------------------
# SSD: chunked == sequential recurrence

@pytest.mark.parametrize("T,chunk", [(32, 8), (64, 16), (128, 128)])
def test_ssd_chunked_matches_reference(T, chunk):
    B, H, P, N = 2, 3, 8, 16
    ks = jax.random.split(KEY, 5)
    xh = jax.random.normal(ks[0], (B, T, H, P))
    Bm = jax.random.normal(ks[1], (B, T, N)) * 0.5
    Cm = jax.random.normal(ks[2], (B, T, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)) - 1.0)
    A_log = jax.random.normal(ks[4], (H,)) * 0.3
    D = jnp.ones((H,))
    y_ref = ssd_reference(xh, Bm, Cm, dt, A_log, D)
    y, final = ssd_chunked(xh, Bm, Cm, dt, A_log, D, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)


def test_ssd_final_state_continues_decode():
    """Prefill state + decode steps == running the full sequence."""
    B, T, H, P, N = 1, 24, 2, 4, 8
    ks = jax.random.split(KEY, 5)
    xh = jax.random.normal(ks[0], (B, T + 4, H, P))
    Bm = jax.random.normal(ks[1], (B, T + 4, N)) * 0.5
    Cm = jax.random.normal(ks[2], (B, T + 4, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, T + 4, H)) - 1.0)
    A_log = jax.random.normal(ks[4], (H,)) * 0.3
    D = jnp.zeros((H,))

    y_all = ssd_reference(xh, Bm, Cm, dt, A_log, D)
    _, state = ssd_chunked(xh[:, :T], Bm[:, :T], Cm[:, :T], dt[:, :T],
                           A_log, D, chunk=8)
    A = -jnp.exp(A_log)
    for t in range(T, T + 4):
        dA = jnp.exp(dt[:, t] * A)
        state = state * dA[:, :, None, None] + jnp.einsum(
            "bh,bn,bhp->bhpn", dt[:, t], Bm[:, t], xh[:, t])
        y_t = jnp.einsum("bn,bhpn->bhp", Cm[:, t], state)
        np.testing.assert_allclose(np.asarray(y_t), np.asarray(y_all[:, t]),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# attention

def _ref_attention(q, k, v, causal=True, window=None, softcap=None,
                   q_offset=0):
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    kk = jnp.repeat(k, G, axis=2)
    vv = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bshd->bhqs", q, kk) / np.sqrt(hd)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Sk)
    m = jnp.ones((Sq, Sk), bool)
    if causal:
        m &= kpos[None] <= qpos[:, None]
    if window:
        m &= kpos[None] > qpos[:, None] - window
    s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqs,bshd->bqhd", p, vv)


@pytest.mark.parametrize("H,K", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("window,softcap", [(None, None), (6, None),
                                            (None, 30.0)])
def test_gqa_attention_vs_reference(H, K, window, softcap):
    B, S, hd = 2, 16, 8
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    out = gqa_attention(q, k, v, causal=True, window=window, softcap=softcap)
    ref = _ref_attention(q, k, v, causal=True, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_decode_attention_matches_last_row_of_full():
    B, S, H, K, hd = 2, 12, 4, 2, 8
    ks = jax.random.split(KEY, 3)
    q_all = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    full = _ref_attention(q_all, k, v, causal=True)
    out = decode_attention(q_all[:, -1:], k, v, cache_len=S)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(full[:, -1]),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# decode == teacher-forced forward (whole model, per family)

@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "gemma2-2b", "mamba2-130m",
                                  "dbrx-132b", "whisper-base",
                                  "internvl2-76b", "jamba-1.5-large-398b"])
def test_decode_consistent_with_prefill(arch):
    """prefill(t[0:n]) then decode(t[n]) must equal prefill(t[0:n+1])'s
    last-token logits (greedy serving correctness)."""
    m = get_model(reduced(get_config(arch)))
    cfg = m.cfg
    params = m.init(KEY)
    B, S = 2, 17
    batch = m.make_batch(jax.random.PRNGKey(5), "prefill", B, S)
    toks = batch["tokens"]

    b_short = dict(batch, tokens=toks[:, :-1])
    _, cache = jax.jit(lambda p, b: m.prefill(p, b, pad_to=S + 4))(
        params, b_short)
    logits_dec, _ = jax.jit(m.decode)(params, cache, {"tokens": toks[:, -1:]})

    logits_full, _ = jax.jit(lambda p, b: m.prefill(p, b))(params, batch)
    np.testing.assert_allclose(np.asarray(logits_dec),
                               np.asarray(logits_full),
                               rtol=2e-3, atol=2e-3)


def test_windowed_ring_cache_decode():
    """With window W << S the ring cache must reproduce windowed attention."""
    arch = get_config("gemma2-2b", long_context=True)
    from repro.models.common import reduced as _red
    cfg = _red(arch)
    # shrink window so S > W exercises the ring
    from dataclasses import replace
    pat = tuple(replace(s, window=8) for s in cfg.pattern)
    cfg = replace(cfg, pattern=pat)
    m = get_model(cfg)
    params = m.init(KEY)
    B, S = 1, 21
    batch = m.make_batch(jax.random.PRNGKey(9), "prefill", B, S)
    toks = batch["tokens"]
    _, cache = jax.jit(m.prefill)(params, dict(batch, tokens=toks[:, :-1]))
    logits_dec, _ = jax.jit(m.decode)(params, cache, {"tokens": toks[:, -1:]})
    logits_full, _ = jax.jit(m.prefill)(params, batch)
    np.testing.assert_allclose(np.asarray(logits_dec), np.asarray(logits_full),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# MoE

def test_moe_identity_when_experts_equal():
    """If all experts share weights, MoE == the single dense expert."""
    from repro.models.moe import moe_block
    from repro.models.layers import mlp_block
    cfg = reduced(get_config("dbrx-132b"))
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(KEY, 4)
    wg = jax.random.normal(ks[0], (D, F)) * 0.05
    wu = jax.random.normal(ks[1], (D, F)) * 0.05
    wd = jax.random.normal(ks[2], (F, D)) * 0.05
    p = {"router": jax.random.normal(ks[3], (D, E)),
         "wg": jnp.tile(wg, (E, 1, 1)), "wu": jnp.tile(wu, (E, 1, 1)),
         "wd": jnp.tile(wd, (E, 1, 1))}
    x = jax.random.normal(KEY, (2, 8, D)) * 0.5
    from dataclasses import replace
    cfg2 = replace(cfg, capacity_factor=8.0)  # no drops
    y, aux = moe_block(p, x, cfg2)
    y_dense = mlp_block({"wg": wg, "wu": wu, "wd": wd}, x, "swiglu")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense),
                               rtol=2e-3, atol=2e-3)
    assert float(aux["load_balance"]) >= 0.99  # >= 1 ideal balance


def test_moe_capacity_drops_tokens():
    """The capacity path (what a layer spread over several devices runs)
    drops what overflows its capacity; one chip's layer runs drop-free."""
    from repro.models.moe import moe_capacity
    cfg = reduced(get_config("dbrx-132b"))
    from dataclasses import replace
    cfg = replace(cfg, capacity_factor=0.1)  # force overflow
    D, E = cfg.d_model, cfg.n_experts
    ks = jax.random.split(KEY, 5)
    p = {"router": jax.random.normal(ks[0], (D, E)),
         "wg": jax.random.normal(ks[1], (E, D, cfg.d_ff)) * 0.05,
         "wu": jax.random.normal(ks[2], (E, D, cfg.d_ff)) * 0.05,
         "wd": jax.random.normal(ks[3], (E, cfg.d_ff, D)) * 0.05}
    x = jax.random.normal(ks[4], (1, 64, D))
    y, _ = moe_capacity(p, x, cfg)
    # dropped tokens produce zero output rows — at least some survive
    norms = np.linalg.norm(np.asarray(y), axis=-1)
    assert (norms > 1e-6).any() and (norms <= 1e-6).any()
    assert np.all(np.isfinite(np.asarray(y)))
    from repro.models.moe import moe_block
    y1, _ = moe_block(p, x, cfg)                # one chip: drop-free
    assert (np.linalg.norm(np.asarray(y1), axis=-1) > 1e-6).all()
