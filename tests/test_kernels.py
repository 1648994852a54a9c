"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Shape/dtype sweeps for each kernel plus hypothesis property tests for the
fused-update (the KVStore updater big-op).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_update import sgd_momentum
from repro.kernels.rmsnorm import rmsnorm

KEY = jax.random.PRNGKey(3)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention

ATTN_SHAPES = [
    # B, Sq, Sk, H, K, hd
    (2, 128, 128, 4, 2, 64),
    (1, 256, 256, 8, 8, 64),     # MHA
    (2, 64, 64, 4, 1, 128),      # MQA
    (1, 200, 200, 4, 2, 64),     # non-multiple of block
    (2, 8, 8, 2, 2, 32),         # tiny
    (1, 384, 384, 2, 2, 256),    # gemma head_dim
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(shape, dtype):
    B, Sq, Sk, H, K, hd = shape
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Sk, K, hd), dtype)
    v = jax.random.normal(ks[2], (B, Sk, K, hd), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("window", [16, 64])
def test_flash_attention_window(window):
    B, S, H, K, hd = 1, 128, 4, 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    out = flash_attention(q, k, v, causal=True, window=window, block_q=32,
                          block_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_softcap():
    B, S, H, K, hd = 2, 96, 4, 4, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd)) * 3
    k = jax.random.normal(ks[1], (B, S, K, hd)) * 3
    v = jax.random.normal(ks[2], (B, S, K, hd))
    out = flash_attention(q, k, v, causal=True, softcap=30.0, block_q=32,
                          block_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=True, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_flash_attention_decode_offset():
    """Sq=1 with a long kv and q_offset (serving path)."""
    B, Sk, H, K, hd = 2, 300, 8, 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd))
    k = jax.random.normal(ks[1], (B, Sk, K, hd))
    v = jax.random.normal(ks[2], (B, Sk, K, hd))
    out = flash_attention(q, k, v, causal=True, q_offset=Sk - 1,
                          block_k=128)
    want = ref.flash_attention_ref(q, k, v, causal=True, q_offset=Sk - 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("window", [None, 32])
@pytest.mark.parametrize("softcap", [None, 20.0])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_flash_attention_matrix(causal, window, softcap, group):
    """Full causal × sliding-window × softcap × GQA-group matrix vs the
    jnp oracle (interpret mode) — ISSUE-3 satellite coverage."""
    if window is not None and not causal:
        pytest.skip("windowed layers are causal in every config")
    B, S, K, hd = 1, 128, 2, 32
    H = K * group
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd)) * 2
    k = jax.random.normal(ks[1], (B, S, K, hd)) * 2
    v = jax.random.normal(ks[2], (B, S, K, hd))
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# carry mode: the per-ring-step contract (DESIGN.md §8)

def test_flash_attention_carry_chain_matches_full():
    """Chaining per-chunk passes through (m, l, acc) + kv_offset equals
    one full pass — the invariant dist/ring.py is built on."""
    from repro.kernels.flash_attention import flash_carry_finalize
    B, S, H, K, hd = 2, 192, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    for kw in (dict(causal=True), dict(causal=True, window=80),
               dict(causal=True, softcap=25.0), dict(causal=False)):
        want = ref.flash_attention_ref(q, k, v, **kw)
        st = None
        for c0 in range(0, S, 64):
            st = flash_attention(q, k[:, c0:c0 + 64], v[:, c0:c0 + 64],
                                 carry=st, kv_offset=c0, return_carry=True,
                                 block_q=32, block_k=32, **kw)
        out, lse = flash_carry_finalize(st, q.dtype)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)
        assert lse.shape == (B, S, H)
        assert np.isfinite(np.asarray(lse)).all()


def test_flash_attention_neutral_carry_is_identity():
    """Seeding with the neutral (−inf, 0, 0) state changes nothing."""
    from repro.kernels.flash_attention import (flash_carry_finalize,
                                               flash_carry_init)
    B, S, H, K, hd = 1, 64, 2, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    base = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    st = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                         carry=flash_carry_init(B, S, H, hd),
                         return_carry=True)
    out, _ = flash_carry_finalize(st, q.dtype)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=2e-6, atol=2e-6)


def test_flash_carry_lse_matches_logsumexp():
    from repro.kernels.flash_attention import flash_carry_finalize
    B, S, H, K, hd = 1, 96, 2, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    st = flash_attention(q, k, v, causal=True, return_carry=True,
                         block_q=32, block_k=32)
    _, lse = flash_carry_finalize(st)
    s = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32),
                   jnp.repeat(k, 1, 2).astype(jnp.float32)) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -1e30)
    want = jax.scipy.special.logsumexp(s, axis=-1).transpose(0, 2, 1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_flash_attention_kv_len_masking():
    """Padded cache: keys beyond kv_len are invisible."""
    B, S, H, K, hd = 1, 64, 2, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    out = flash_attention(q, k, v, causal=False, kv_len=40, block_q=32,
                          block_k=32)
    want = ref.flash_attention_ref(q[:, :, :, :], k[:, :40], v[:, :40],
                                   causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# paged attention (ISSUE 4, DESIGN.md §9)

def _lanes(pool):
    """(..., K, hd) rows -> the lane-dense (..., K*hd) rows the stacked
    pool stores."""
    return pool.reshape(*pool.shape[:-2], -1)


def _paged_case(B, H, K, hd, bs, NB, P, lengths, seed=5, L=1):
    """q, stacked lane-dense pools (L, NB, bs, K*hd) with distinct random
    layers, block tables and lengths."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    kp = jax.random.normal(ks[1], (L, NB, bs, K * hd))
    vp = jax.random.normal(ks[2], (L, NB, bs, K * hd))
    # distinct physical blocks per (seq, page), none using the sink 0
    tables = (1 + jnp.arange(B * P, dtype=jnp.int32) % (NB - 1)).reshape(B, P)
    return q, kp, vp, tables, jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("H,K", [(4, 4), (4, 2), (8, 1)])  # MHA, GQA, MQA
def test_paged_attention_gqa_vs_ref(H, K):
    from repro.kernels.paged_attention import paged_attention
    q, kp, vp, tables, lengths = _paged_case(
        B=3, H=H, K=K, hd=32, bs=8, NB=16, P=4, lengths=[19, 8, 1])
    out = paged_attention(q, kp, vp, tables, lengths, 0)
    want = ref.paged_attention_ref(q, kp, vp, tables, lengths, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("lengths", [[8, 16, 24, 32],    # exact boundaries
                                     [7, 9, 17, 31],     # straddling
                                     [1, 2, 33, 40]])    # edges + full
def test_paged_attention_block_boundaries(lengths):
    from repro.kernels.paged_attention import paged_attention
    q, kp, vp, tables, lengths = _paged_case(
        B=4, H=4, K=2, hd=64, bs=8, NB=24, P=5, lengths=lengths)
    out = paged_attention(q, kp, vp, tables, lengths, 0)
    want = ref.paged_attention_ref(q, kp, vp, tables, lengths, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window,softcap", [(6, None), (None, 20.0),
                                            (16, 30.0)])
def test_paged_attention_window_softcap(window, softcap):
    from repro.kernels.paged_attention import paged_attention
    q, kp, vp, tables, lengths = _paged_case(
        B=2, H=4, K=2, hd=32, bs=8, NB=12, P=3, lengths=[21, 13])
    q = q * 3                                   # exercise the softcap
    out = paged_attention(q, kp, vp, tables, lengths, 0, window=window,
                          softcap=softcap)
    want = ref.paged_attention_ref(q, kp, vp, tables, lengths, 0,
                                   window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("H,K,hd", [(16, 16, 64),     # qwen1.5-0.5b heads
                                    (48, 4, 128)])    # starcoder2, G 12
@pytest.mark.parametrize("variant", ["plain", "window", "softcap", "int8"])
def test_paged_attention_stacked_layer_vs_ref(layer, H, K, hd, variant):
    """The kernel reads layer ``layer`` of a three-layer stacked pool as
    it lies, cutting its heads out of the lane-dense rows: it matches the
    oracle on the stacked pool, and the oracle matches itself on that one
    layer alone (so neither reads another layer)."""
    from repro.kernels.paged_attention import paged_attention
    q, kp, vp, tables, lengths = _paged_case(
        B=2, H=H, K=K, hd=hd, bs=8, NB=7, P=3, lengths=[21, 9], L=3)
    kw = {"window": 10} if variant == "window" else \
        {"softcap": 20.0} if variant == "softcap" else {}
    if variant == "softcap":
        q = q * 3
    if variant == "int8":
        from repro.kernels.quant import kv_quantize_rows
        split = (3, 7, 8, K, hd)
        kp, kw["k_scale"] = kv_quantize_rows(kp.reshape(split), jnp.int8)
        vp, kw["v_scale"] = kv_quantize_rows(vp.reshape(split), jnp.int8)
        kp, vp = _lanes(kp), _lanes(vp)
    out = paged_attention(q, kp, vp, tables, lengths, layer, **kw)
    want = ref.paged_attention_ref(q, kp, vp, tables, lengths, layer, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    one = slice(layer, layer + 1)
    alone = {k: (v[one] if k.endswith("scale") else v)
             for k, v in kw.items()}
    want1 = ref.paged_attention_ref(q, kp[one], vp[one], tables, lengths, 0,
                                    **alone)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(want1))


def test_paged_attention_matches_contiguous_flash():
    """A paged sequence must attend identically to the same K/V laid out
    contiguously (flash decode with q_offset) — table indirection is
    layout only."""
    B, H, K, hd, bs, P = 1, 4, 2, 32, 8, 4
    S = 27                                      # straddles 4 pages
    ks = jax.random.split(KEY, 3)
    q1 = jax.random.normal(ks[0], (B, 1, H, hd))
    k = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    want = ref.flash_attention_ref(q1, k, v, causal=True, q_offset=S - 1)
    # scatter the contiguous rows into shuffled physical blocks
    order = np.asarray([3, 1, 4, 2])            # physical block per page
    kp = np.zeros((1, 6, bs, K, hd), np.float32)
    vp = np.zeros((1, 6, bs, K, hd), np.float32)
    for page in range(P):
        rows = np.asarray(k[0, page * bs:(page + 1) * bs])
        kp[0, order[page], :rows.shape[0]] = rows
        rows = np.asarray(v[0, page * bs:(page + 1) * bs])
        vp[0, order[page], :rows.shape[0]] = rows
    from repro.kernels.paged_attention import paged_attention
    out = paged_attention(q1[:, 0], jnp.asarray(_lanes(kp)),
                          jnp.asarray(_lanes(vp)),
                          jnp.asarray(order[None], jnp.int32),
                          jnp.asarray([S], jnp.int32), 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want[:, 0]),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_zero_length_lane_is_zero():
    from repro.kernels.paged_attention import paged_attention
    q, kp, vp, tables, _ = _paged_case(
        B=2, H=4, K=2, hd=32, bs=8, NB=12, P=3, lengths=[5, 0])
    out = paged_attention(q, kp, vp, tables,
                          jnp.asarray([5, 0], jnp.int32), 0)
    assert np.abs(np.asarray(out[1])).max() == 0.0
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("step", ["decode", "prefill"])
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
@pytest.mark.parametrize("n_layers", [2, 6])           # unrolled, lax.scan
def test_paged_step_writes_only_its_rows(step, kv_dtype, n_layers):
    """One serving step leaves every row of every layer of the stacked
    pools bitwise as it was, except the rows it wrote at (layer, block,
    offset), and each of those holds that layer's own K/V of its token.
    The blocks' output projections are zeroed, so every layer sees the
    token's embedding and its rows can be computed alone; a row written
    into another layer, block or offset fails one of the two checks."""
    from repro.configs import get_config
    from repro.models import get_model, layers, reduced, transformer
    from repro.kernels.quant import kv_dequantize
    cfg = reduced(get_config("qwen1.5-0.5b"), n_layers=n_layers)
    model = get_model(cfg)
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a)
        if path[-1].key in ("wo", "wd") else a, model.init(KEY))
    cache = model.make_paged_cache(9, 4, 3, kv_dtype=kv_dtype)
    keys = iter(jax.random.split(KEY, 16))
    noise = lambda a: (jax.random.normal(next(keys), a.shape) * 4).astype(
        a.dtype)                      # every row distinct from a new one
    cache = jax.tree.map(noise, cache)
    if step == "decode":
        # lane 0 at position 5 (its page 1, block 2, offset 1), lane 1 at
        # 2 (block 3, offset 2), lane 2 idle on the sink
        batch = {"tokens": jnp.asarray([[7], [8], [0]], jnp.int32),
                 "block_tables": jnp.asarray([[1, 2], [3, 4], [0, 0]],
                                             jnp.int32),
                 "pos": jnp.asarray([5, 2, 0], jnp.int32),
                 "active": jnp.asarray([True, True, False])}
        written = {(2, 1): (7, 5), (3, 2): (8, 2)}   # row: (token, pos)
        fn = model.decode_paged
    else:
        # positions 2..4 of the slot's blocks (1, 2), one pad row -> sink
        batch = {"tokens": jnp.asarray([[5, 6, 7, 0]], jnp.int32),
                 "block_tables": jnp.asarray([[1, 2]], jnp.int32),
                 "start": jnp.asarray(2, jnp.int32),
                 "length": jnp.asarray(3, jnp.int32),
                 "slot": jnp.asarray(0, jnp.int32)}
        written = {(1, 2): (5, 2), (1, 3): (6, 3), (2, 0): (7, 4)}
        fn = model.prefill_chunk_paged
    _, new = jax.jit(fn)(params, cache, batch)
    old, got = cache["layers"]["p0"], new["layers"]["p0"]
    for key in old:
        a, b = np.asarray(old[key]), np.asarray(got[key])
        assert b.shape == a.shape == (n_layers, 9, 4) + a.shape[3:]
        same = (a == b).reshape(n_layers, 9, 4, -1).all(-1)
        for blk, off in written:
            same[:, blk, off] = True
        same[:, 0, 0] = True           # the sink takes idle and pad rows
        assert same.all(), (key, np.argwhere(~same))

    def own_rows(layer, token, pos):
        bp = jax.tree.map(lambda a: a[layer], params["blocks"])["p0"]
        x = transformer.embed_tokens(params, jnp.asarray([[token]]), cfg)
        _, k, v = layers.attn_project_qkv(
            bp["attn"], layers.rmsnorm(x, bp["ln1"], cfg.norm_eps), cfg)
        cos, sin = layers.rope_freqs(jnp.asarray([[pos]]), cfg.hd,
                                     cfg.rope_theta)
        return {"k": layers.apply_rope(k, cos, sin)[0, 0], "v": v[0, 0]}

    for layer in range(n_layers):
        for (blk, off), (token, pos) in written.items():
            want = own_rows(layer, token, pos)
            for kv in ("k", "v"):
                row = got[kv][layer, blk, off].reshape(want[kv].shape)
                step_ = 1e-5
                if kv_dtype is not None:
                    scale = got[f"{kv}_scale"][layer, blk, off]
                    row, step_ = kv_dequantize(row, scale), float(scale.max())
                np.testing.assert_allclose(np.asarray(row),
                                           np.asarray(want[kv]),
                                           rtol=1e-5, atol=step_)


# ---------------------------------------------------------------------------
# paged attention schedule tunables (DESIGN.md §13): pages_per_step /
# head_tile never change results, only the grid

@pytest.mark.parametrize("pps", [1, 2, 4, 5])
@pytest.mark.parametrize("ht", [8, 16])
def test_paged_attention_schedule_tunables(pps, ht):
    from repro.kernels.paged_attention import paged_attention
    q, kp, vp, tables, lengths = _paged_case(
        B=3, H=16, K=16, hd=32, bs=8, NB=17, P=5, lengths=[19, 33, 40])
    out = paged_attention(q, kp, vp, tables, lengths, 0,
                          pages_per_step=pps, head_tile=ht)
    want = ref.paged_attention_ref(q, kp, vp, tables, lengths, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# quantized paged KV-cache (int8 / fp8, DESIGN.md §13)

def _quantize_case(kv_dtype, **kw):
    """A paged case with its pools quantized per (token, kv-head): codes
    back in the lane-dense rows, scales (L, NB, bs, K)."""
    from repro.kernels.quant import kv_quantize_rows
    q, kp, vp, tables, lengths = _paged_case(**kw)
    split = (*kp.shape[:3], kw["K"], kw["hd"])
    kq, ks = kv_quantize_rows(kp.reshape(split), kv_dtype)
    vq, vs = kv_quantize_rows(vp.reshape(split), kv_dtype)
    return q, (kp, vp), (_lanes(kq), _lanes(vq), ks, vs), tables, lengths


@pytest.mark.parametrize("kv_dtype,fp_tol", [
    ("int8", 2.5e-2), ("fp8_e4m3", 1e-1), ("fp8_e5m2", 2e-1)])
def test_paged_attention_quantized(kv_dtype, fp_tol):
    """Kernel with quantized pools: (a) must equal the quantized ORACLE
    tightly — the fused dequant is the same math; (b) must stay within
    the quantization error budget of full-precision attention."""
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.quant import resolve_kv_dtype
    q, (kp, vp), (kq, vq, ks, vs), tables, lengths = _quantize_case(
        resolve_kv_dtype(kv_dtype),
        B=3, H=4, K=2, hd=64, bs=8, NB=16, P=4, lengths=[19, 8, 31])
    out = paged_attention(q, kq, vq, tables, lengths, 0,
                          k_scale=ks, v_scale=vs)
    qref = ref.paged_attention_ref(q, kq, vq, tables, lengths, 0,
                                   k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(qref),
                               rtol=2e-5, atol=2e-5)
    fpref = ref.paged_attention_ref(q, kp, vp, tables, lengths, 0)
    assert np.abs(np.asarray(out) - np.asarray(fpref)).max() < fp_tol


def test_paged_attention_quantized_with_schedule_and_window():
    from repro.kernels.paged_attention import paged_attention
    q, _, (kq, vq, ks, vs), tables, lengths = _quantize_case(
        jnp.int8, B=2, H=4, K=2, hd=32, bs=8, NB=12, P=3, lengths=[21, 13])
    want = ref.paged_attention_ref(q, kq, vq, tables, lengths, 0,
                                   k_scale=ks, v_scale=vs, window=6)
    out = paged_attention(q, kq, vq, tables, lengths, 0, k_scale=ks,
                          v_scale=vs, window=6, pages_per_step=2,
                          head_tile=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_kv_quantize_roundtrip():
    from repro.kernels.quant import (kv_dequantize, kv_quantize_rows,
                                     resolve_kv_dtype)
    x = jax.random.normal(KEY, (6, 8, 2, 64)) * 3
    for name, tol_ in (("int8", 2e-2), ("fp8_e4m3", 2e-1)):
        qx, s = kv_quantize_rows(x, resolve_kv_dtype(name))
        assert s.shape == x.shape[:-1]
        back = kv_dequantize(qx, s)
        assert np.abs(np.asarray(back - x)).max() < tol_ * 3
    # all-zero rows survive (scale 0 -> dequant to exact 0, no NaN)
    qz, sz = kv_quantize_rows(jnp.zeros((2, 4, 1, 8)),
                              resolve_kv_dtype("int8"))
    assert np.abs(np.asarray(kv_dequantize(qz, sz))).max() == 0.0
    with pytest.raises(ValueError):
        resolve_kv_dtype("int4")


# ---------------------------------------------------------------------------
# fused top-k/top-p sampling kernel vs the ref oracle (DESIGN.md §13)

SAMPLE_CONFIGS = [
    {"temperature": 0.0},                               # greedy
    {"temperature": 1.0},                               # plain categorical
    {"temperature": 1.0, "top_k": 1},                   # degenerate argmax
    {"temperature": 0.7, "top_k": 8},
    {"temperature": 0.7, "top_p": 0.8},
    {"temperature": 0.9, "top_p": 0.999},               # keeps ~everything
    {"temperature": 0.8, "top_k": 50, "top_p": 0.9},    # both filters
]


@pytest.mark.parametrize("kw", SAMPLE_CONFIGS)
def test_sampling_kernel_matches_ref(kw):
    from repro.kernels.sampling import sample_tokens
    kk = jax.random.split(jax.random.PRNGKey(17), 2)
    logits = jax.random.normal(kk[0], (7, 257)) * 3.0   # odd B and V
    u = jax.random.uniform(kk[1], (7,))
    got = np.asarray(sample_tokens(logits, u, **kw))
    want = np.asarray(ref.sample_ref(logits, u, **kw))
    np.testing.assert_array_equal(got, want)


def test_sampling_top_k_support():
    """Every draw over many uniforms lies in the true top-k set."""
    from repro.kernels.sampling import sample_tokens
    logits = jax.random.normal(jax.random.PRNGKey(5), (1, 101)) * 2
    topk = set(np.asarray(jax.lax.top_k(logits, 8)[1])[0].tolist())
    us = jnp.linspace(0.001, 0.999, 41)
    for u in us:
        t = int(sample_tokens(logits, u[None], temperature=1.0, top_k=8)[0])
        assert t in topk


def test_sampling_top_p_support():
    """Draws live in the smallest nucleus with mass >= p (ties included)."""
    from repro.kernels.sampling import sample_tokens
    logits = jax.random.normal(jax.random.PRNGKey(6), (1, 64)) * 3
    p = jax.nn.softmax(logits, -1)[0]
    order = np.argsort(-np.asarray(p))
    cum = np.cumsum(np.asarray(p)[order])
    n_keep = int(np.searchsorted(cum, 0.8)) + 1
    nucleus = set(order[:n_keep].tolist())
    for u in jnp.linspace(0.01, 0.99, 23):
        t = int(sample_tokens(logits, u[None], temperature=1.0,
                              top_p=0.8)[0])
        assert t in nucleus


def test_sampling_rows_per_step_is_schedule_only():
    from repro.kernels.sampling import sample_tokens
    kk = jax.random.split(jax.random.PRNGKey(8), 2)
    logits = jax.random.normal(kk[0], (6, 130)) * 2
    u = jax.random.uniform(kk[1], (6,))
    base = np.asarray(sample_tokens(logits, u, temperature=0.8, top_k=10,
                                    top_p=0.95, rows_per_step=4))
    for rps in (1, 3, 8):
        got = np.asarray(sample_tokens(logits, u, temperature=0.8,
                                       top_k=10, top_p=0.95,
                                       rows_per_step=rps))
        np.testing.assert_array_equal(got, base)


# ---------------------------------------------------------------------------
# rmsnorm

@pytest.mark.parametrize("shape", [(4, 64), (3, 5, 128), (1, 2048),
                                   (17, 300), (128, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_shapes_dtypes(shape, dtype):
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], shape, dtype)
    w = (jax.random.normal(ks[1], shape[-1:]) * 0.1).astype(dtype)
    out = rmsnorm(x, w, block_rows=8)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


# ---------------------------------------------------------------------------
# fused SGD-momentum update (the KVStore updater)

@pytest.mark.parametrize("shape", [(100,), (33, 7), (2, 3, 5, 8), (4096,)])
@pytest.mark.parametrize("pdtype", [jnp.float32, jnp.bfloat16])
def test_fused_update_shapes(shape, pdtype):
    ks = jax.random.split(KEY, 3)
    p = jax.random.normal(ks[0], shape, pdtype)
    g = jax.random.normal(ks[1], shape, pdtype)
    m = jax.random.normal(ks[2], shape, jnp.float32)
    new_p, new_m = sgd_momentum(p, g, m, lr=0.1, mu=0.9, weight_decay=0.01,
                                block=64)
    want_p, want_m = ref.sgd_momentum_ref(p, g, m, lr=0.1, mu=0.9,
                                          weight_decay=0.01)
    np.testing.assert_allclose(np.asarray(new_m), np.asarray(want_m),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(new_p, np.float32),
                               np.asarray(want_p, np.float32), **tol(pdtype))


@given(st.integers(1, 500), st.floats(1e-4, 0.5), st.floats(0.0, 0.99),
       st.floats(0.0, 0.1))
@settings(max_examples=20, deadline=None)
def test_fused_update_property(n, lr, mu, wd):
    """Hypothesis sweep over sizes and hyperparameters."""
    ks = jax.random.split(jax.random.PRNGKey(n), 3)
    p = jax.random.normal(ks[0], (n,))
    g = jax.random.normal(ks[1], (n,))
    m = jax.random.normal(ks[2], (n,))
    new_p, new_m = sgd_momentum(p, g, m, lr=lr, mu=mu, weight_decay=wd,
                                block=128)
    want_p, want_m = ref.sgd_momentum_ref(p, g, m, lr=lr, mu=mu,
                                          weight_decay=wd)
    np.testing.assert_allclose(np.asarray(new_p), np.asarray(want_p),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new_m), np.asarray(want_m),
                               rtol=1e-4, atol=1e-5)


def test_update_is_idempotent_free_and_stateful():
    """Repeated updates track the reference trajectory (momentum state)."""
    p = jnp.ones((64,), jnp.float32)
    g = jnp.full((64,), 0.5)
    m = jnp.zeros((64,), jnp.float32)
    pr, mr = p, m
    for _ in range(5):
        p, m = sgd_momentum(p, g, m, lr=0.1, mu=0.9, weight_decay=0.0,
                            block=64)
        pr, mr = ref.sgd_momentum_ref(pr, g, mr, lr=0.1, mu=0.9,
                                      weight_decay=0.0)
    np.testing.assert_allclose(np.asarray(p), np.asarray(pr), rtol=1e-6)


# ---------------------------------------------------------------------------
# model integration: Pallas attention == jnp attention inside a real model

def test_model_with_pallas_attention_matches():
    from repro.configs import get_config
    from repro.models import get_model, reduced
    from repro.models import layers as L
    m = get_model(reduced(get_config("qwen1.5-0.5b")))
    params = m.init(KEY)
    batch = m.make_batch(KEY, "train", 1, 64)
    loss0, _ = m.loss(params, batch)
    L.set_use_pallas(True)
    try:
        loss1, _ = m.loss(params, batch)
    finally:
        L.set_use_pallas(False)
    np.testing.assert_allclose(float(loss0), float(loss1), rtol=1e-4)
