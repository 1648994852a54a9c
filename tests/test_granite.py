"""granite-4.0-h-small at a tiny size on the CPU: the program against the
plain reference the benchmark's configuration names
(``bench/reference/granite_hybrid.py``), the held share of the experts
against the uncut layer, the grouped expert kernel against its oracle,
each of Granite's mechanisms against a hand computation, the
configuration file against the program, the parameter count at the
published sizes, and the normal serving entry point."""
from __future__ import annotations

import copy
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common
from repro import obs
from repro.configs import get_config
from repro.kernels import registry
from repro.kernels.expert_gmm import expert_gmm
from repro.kernels.ref import expert_gmm_ref
from repro.models import get_model, reduced

WORKLOAD = "granite-4.0-h-small-pp4-ep8.rag-chat"
REF = common.load_reference("granite_hybrid")
KEY = jax.random.PRNGKey(0)

# the cell's configuration at a tiny size: every key as the file states it
# but the widths, 8 routed experts of which 4 are held, f32
TINY = {"num_hidden_layers": 10, "hidden_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
        "intermediate_size": 128, "shared_intermediate_size": 192,
        "num_routed_experts": 8, "num_local_experts": 4, "expert_offset": 4,
        "num_experts_per_tok": 3, "mamba_d_state": 16, "mamba_n_heads": 8,
        "mamba_d_head": 64, "mamba_chunk_size": 8, "vocab_size": 512,
        "dtype": "float32"}


def _tiny(**over):
    """(conf, cfg, dims) of the cell's configuration at the tiny size."""
    conf = copy.deepcopy(common.load_cell(WORKLOAD).config)
    conf["model"].update(TINY, **over)
    m = conf["model"]
    di = m["mamba_expand"] * m["hidden_size"]
    conf["kv_pool"].update(
        bytes_per_token=2 * m["num_key_value_heads"] * m["head_dim"] * 4,
        state_bytes_per_lane=9 * (
            3 * (di + 2 * m["mamba_d_state"]) * 4
            + m["mamba_n_heads"] * m["mamba_d_head"] * m["mamba_d_state"] * 4))
    return conf, common.arch_config(conf, REF), REF.dims_of(conf)


def _prefill_then_decode(model, params, prompt, *, chunk, steps, lane=1):
    """Logits of the prompt's last token, through paged prefill chunks
    into ``lane``, then of ``steps`` greedy decode steps beside an idle
    lane; the tokens, the cache, and each program's counts."""
    bs, P = 8, 8
    cache = model.make_paged_cache(1 + 2 * P, bs, 2)
    table = jnp.arange(1, P + 1, dtype=jnp.int32)[None]
    got, counts = [], []
    for start in range(0, len(prompt), chunk):
        part = prompt[start:start + chunk]
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :len(part)] = part
        logits, cache = model.prefill_chunk_paged(params, cache, {
            "tokens": jnp.asarray(toks), "block_tables": table,
            "start": jnp.asarray(start, jnp.int32),
            "length": jnp.asarray(len(part), jnp.int32),
            "slot": jnp.asarray(lane, jnp.int32)})
        counts.append(jax.device_get(cache["counts"]))
    got.append(logits[0])
    seq = list(prompt)
    tables = jnp.zeros((2, P), jnp.int32).at[lane].set(table[0])
    for _ in range(steps):
        nxt = int(jnp.argmax(got[-1]))
        tok = np.zeros((2, 1), np.int32)
        tok[lane] = nxt
        pos = np.zeros(2, np.int32)
        pos[lane] = len(seq)
        seq.append(nxt)
        logits, cache = model.decode_paged(params, cache, {
            "tokens": jnp.asarray(tok), "block_tables": tables,
            "pos": jnp.asarray(pos),
            "active": jnp.asarray(np.arange(2) == lane)})
        got.append(logits[lane])
        counts.append(jax.device_get(cache["counts"]))
    return np.stack(got), seq, cache, counts


@pytest.mark.parametrize("held", [(4, 4), (8, 0)])
def test_paged_prefill_then_decode_matches_reference(held):
    """Prefill through three chunks, then decode, against the reference's
    full forward: a held share (experts 4-7 of 8) and the whole layer."""
    conf, cfg, d = _tiny(num_local_experts=held[0], expert_offset=held[1])
    model = get_model(cfg)
    params = common.make_params(cfg, 3, REF, d)
    prompt = np.random.default_rng(0).integers(1, cfg.vocab, 37)
    got, seq, cache, _ = _prefill_then_decode(model, params, prompt,
                                              chunk=16, steps=5)
    rows = jnp.arange(len(prompt) - 1, len(seq))
    want = REF.logits_at(params, jnp.asarray(seq, jnp.int32), rows, d)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-4, atol=1e-5)
    # the idle lane's states were never written
    for name, layer in cache["layers"].items():
        if "ssm" in layer:
            assert not np.any(np.asarray(layer["ssm"][:, 0])), name
            assert not np.any(np.asarray(layer["conv"][:, 0])), name


def test_step_counts_are_the_rows_the_held_experts_received():
    """With every expert held, each real token sends top_k rows into each
    of the ten expert layers: a chunk's padded tail and an idle lane send
    none."""
    conf, cfg, d = _tiny(num_local_experts=8, expert_offset=0)
    model = get_model(cfg)
    params = common.make_params(cfg, 5, REF, d)
    prompt = np.random.default_rng(1).integers(1, cfg.vocab, 21)
    _, _, _, counts = _prefill_then_decode(model, params, prompt, chunk=16,
                                           steps=2)
    per_token = cfg.top_k * cfg.n_layers
    assert [int(c["expert_rows"]) for c in counts] == [
        16 * per_token, 5 * per_token, per_token, per_token]
    assert all(0 < int(c["expert_rows_max"]) <= int(c["expert_rows"])
               for c in counts)


def test_held_shares_sum_to_the_uncut_layer():
    """model-configs §4: at 4 experts of 8, the program's layer holding
    experts 0-3 and the one holding 4-7, with the shared expert that
    both compute counted once, add up to the reference's uncut layer."""
    from repro.models.moe import moe_block
    _, whole, d = _tiny(num_local_experts=8, expert_offset=0)
    params = common.make_params(whole, 7, REF, d)
    p = jax.tree.map(lambda a: a[0], params["blocks"]["p0"]["moe"])
    x = jax.random.normal(KEY, (2, 9, whole.d_model))
    outs = []
    for lo in (0, 4):
        share = replace(whole, experts_held=4, expert_offset=lo)
        ps = dict(p, **{k: p[k][lo:lo + 4] for k in ("wg", "wu", "wd")})
        outs.append(moe_block(ps, x, share)[0])
    g = x @ p["shared_wg"]
    shared = (g * jax.nn.sigmoid(g) * (x @ p["shared_wu"])) @ p["shared_wd"]
    want = jax.vmap(lambda xb: REF.experts(xb, p, d))(x)
    np.testing.assert_allclose(np.asarray(outs[0] + outs[1] - shared),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    # each share alone is short of the whole
    assert float(jnp.abs(outs[0] - want).max()) > 1e-2


@pytest.mark.parametrize("sizes,tm", [((9, 0, 30, 3), 16),
                                      ((0, 0, 5, 0), 8),
                                      ((40, 1, 0, 17), 16)])
def test_expert_gmm_matches_its_oracle(sizes, tm):
    """The grouped matmul in interpret mode against ``kernels/ref.py``:
    groups of every size, empty ones included, and tiles past the live
    ones (left unwritten) ignored."""
    E, D, F = len(sizes), 128, 256
    ks = jax.random.split(KEY, 4)
    padded = [-(-n // tm) * tm for n in sizes]
    tg = np.repeat(np.arange(E), [n // tm for n in padded])
    M = sum(padded) + 2 * tm
    tg = np.concatenate([tg, np.full(M // tm - len(tg), E - 1)])
    args = (jax.random.normal(ks[0], (M, D)),
            jax.random.normal(ks[1], (E, D, F)) * D ** -0.5,
            jax.random.normal(ks[2], (E, D, F)) * D ** -0.5,
            jax.random.normal(ks[3], (E, F, D)) * F ** -0.5,
            jnp.asarray(tg, jnp.int32), jnp.int32(sum(padded) // tm))
    got = expert_gmm(*args, block_rows=tm, block_ff=128, interpret=True)
    want = expert_gmm_ref(*args, block_rows=tm)
    live = sum(padded)
    np.testing.assert_allclose(np.asarray(got[:live]),
                               np.asarray(want[:live]), rtol=2e-5, atol=2e-5)
    assert registry.get("expert_gmm").candidates()[0] == {"block_ff": 256}


# ---------------------------------------------------------------------------
# each mechanism against a hand computation


def _attn_cfg(**over):
    return replace(reduced(get_config("granite-4.0-h-small")), **over)


def test_nope_attention_ignores_positions_and_scales_by_the_multiplier():
    from repro.models.layers import attn_block
    cfg = _attn_cfg()
    spec = cfg.pattern[5]
    H, K, hd, D = cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_model
    ks = jax.random.split(KEY, 5)
    p = {"wq": jax.random.normal(ks[0], (D, H, hd)) * 0.2,
         "wk": jax.random.normal(ks[1], (D, K, hd)) * 0.2,
         "wv": jax.random.normal(ks[2], (D, K, hd)) * 0.2,
         "wo": jax.random.normal(ks[3], (H, hd, D)) * 0.1}
    x = jax.random.normal(ks[4], (1, 6, D))
    out, _ = attn_block(p, x, cfg, spec, positions=jnp.arange(6))
    shifted, _ = attn_block(p, x, cfg, spec, positions=jnp.arange(6) + 1000)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(shifted))
    # by hand: softmax(q k^T / 128) v, causal, kv head h // (H / K)
    q, k, v = (np.einsum("sd,dhk->shk", np.asarray(x[0]), np.asarray(p[n]))
               for n in ("wq", "wk", "wv"))
    want = np.zeros((6, H, hd))
    for h in range(H):
        s = q[:, h] @ k[:, h // (H // K)].T / 128.0
        s = np.where(np.tri(6, dtype=bool), s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        want[:, h] = (w / w.sum(-1, keepdims=True)) @ v[:, h // (H // K)]
    want = np.einsum("shk,hkd->sd", want, np.asarray(p["wo"]))
    np.testing.assert_allclose(np.asarray(out[0]), want, rtol=1e-4,
                               atol=1e-5)


def test_paged_decode_attention_takes_the_score_scale():
    from repro.models.layers import paged_decode_attention
    ks = jax.random.split(KEY, 3)
    B, H, K, hd, bs, P = 2, 4, 2, 8, 4, 2
    q = jax.random.normal(ks[0], (B, H, hd))
    kp = jax.random.normal(ks[1], (1, 1 + B * P, bs, K * hd))
    vp = jax.random.normal(ks[2], (1, 1 + B * P, bs, K * hd))
    tables = jnp.arange(1, 1 + B * P, dtype=jnp.int32).reshape(B, P)
    lengths = jnp.asarray([5, 8], jnp.int32)
    out = paged_decode_attention(q, kp, vp, tables, lengths, 0,
                                 scale=1 / 128)
    for b in range(B):
        rows = np.asarray(kp[0, tables[b]]).reshape(P * bs, K, hd)
        vals = np.asarray(vp[0, tables[b]]).reshape(P * bs, K, hd)
        n = int(lengths[b])
        for h in range(H):
            s = rows[:n, h // 2] @ np.asarray(q[b, h]) / 128.0
            w = np.exp(s - s.max())
            np.testing.assert_allclose(np.asarray(out[b, h]),
                                       (w / w.sum()) @ vals[:n, h // 2],
                                       rtol=1e-5, atol=1e-6)


def test_multipliers_scale_embedding_residual_and_logits():
    from repro.models import transformer as T
    cfg = _attn_cfg()
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (12.0, 0.22, 16.0)
    params = get_model(cfg).init(KEY)
    toks = jnp.asarray([[3, 7]])
    np.testing.assert_allclose(
        np.asarray(T.embed_tokens(params, toks, cfg)),
        12.0 * np.asarray(params["embed"])[[3, 7]][None], rtol=1e-6)
    x = jax.random.normal(KEY, (1, 2, cfg.d_model))
    xn = np.asarray(x) / np.sqrt(np.mean(np.asarray(x) ** 2, -1,
                                         keepdims=True) + cfg.norm_eps)
    np.testing.assert_allclose(
        np.asarray(T.final_logits(params, x, cfg)),
        xn @ np.asarray(params["embed"]).T / 16.0, rtol=1e-4, atol=1e-6)
    # one block: x + 0.22 mixer(n1(x)), then + 0.22 moe(n2(.))
    p = jax.tree.map(lambda a: a[0], params["blocks"]["p0"])
    plain = replace(cfg, residual_multiplier=1.0)
    y, _, _ = T.apply_block(p, x, cfg, cfg.pattern[0])
    zero_moe = dict(p, moe=jax.tree.map(jnp.zeros_like, p["moe"]))
    mixer = T.apply_block(zero_moe, x, plain, cfg.pattern[0])[0] - x
    h = x + 0.22 * mixer
    mlp = T.apply_block(dict(p, ssm=jax.tree.map(jnp.zeros_like, p["ssm"])),
                        h, plain, cfg.pattern[0])[0] - h
    np.testing.assert_allclose(np.asarray(y), np.asarray(h + 0.22 * mlp),
                               rtol=1e-4, atol=1e-5)


def test_mamba_gated_norm_by_hand():
    """out = (w * rmsnorm(y * silu(z))) Wout over all d_inner channels,
    y the SSD output with its skip."""
    from repro.models.ssm import mamba_block, ssd_reference
    cfg = _attn_cfg()
    p = jax.tree.map(lambda a: a[0],
                     get_model(cfg).init(KEY)["blocks"]["p0"]["ssm"])
    p["norm"] = jax.random.normal(KEY, p["norm"].shape) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 5, cfg.d_model))
    out, _ = mamba_block(p, x, cfg)
    di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_p
    zxbcdt = np.asarray(x[0]) @ np.asarray(p["in_proj"])
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * N], \
        zxbcdt[:, 2 * di + 2 * N:]
    w = np.asarray(p["conv_w"])
    pad = np.concatenate([np.zeros((3, xbc.shape[1])), xbc])
    conv = sum(pad[i:i + 5] * w[i] for i in range(4)) + np.asarray(p["conv_b"])
    xbc = conv / (1 + np.exp(-conv))
    xh, bm, cm = xbc[:, :di], xbc[:, di:di + N], xbc[:, di + N:]
    dt = np.log1p(np.exp(dt + np.asarray(p["dt_bias"])))
    y = np.asarray(ssd_reference(
        jnp.asarray(xh.reshape(1, 5, H, P)), jnp.asarray(bm[None]),
        jnp.asarray(cm[None]), jnp.asarray(dt[None]), p["A_log"],
        p["D"]))[0].reshape(5, di)
    g = y * z / (1 + np.exp(-z))
    g = g / np.sqrt(np.mean(g * g, -1, keepdims=True) + cfg.norm_eps)
    want = (g * (1 + np.asarray(p["norm"]))) @ np.asarray(p["out_proj"])
    np.testing.assert_allclose(np.asarray(out[0]), want, rtol=2e-4,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# the configuration file, the parameter count, the entry point


def test_the_cell_loads_and_its_file_states_what_runs():
    cell = common.load_cell(WORKLOAD)
    assert cell.reference.__name__.endswith("granite_hybrid")
    cfg = common.arch_config(cell.config, cell.reference)
    d = cell.dims
    assert (cfg.n_layers, cfg.n_experts, cfg.n_held, cfg.top_k) == (
        10, 72, 9, 10)
    assert [s.kind for s in cfg.pattern] == ["attn" if k == "attention"
                                            else "mamba" for k in d["kinds"]]
    pool = cell.config["kv_pool"]
    assert pool["state_bytes_per_lane"] == common.state_bytes_per_lane(
        cfg) == REF.state_bytes_per_lane(d) == 38_204_928
    assert pool["bytes_per_token"] == common.kv_bytes_per_token(cfg) == 4096
    eng = cell.config["engine"]
    assert pool["num_blocks"] == eng["max_batch"] * (
        eng["max_len"] // eng["block_size"]) + 1
    shapes = jax.eval_shape(get_model(cfg).init, KEY)
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert REF.weight_bytes(d) == nbytes
    assert cfg.param_count() == pytest.approx(2.41e9, rel=0.01)
    # the catalog's numbers at the file's top level, the cut ones listed
    assert cell.config["num_local_experts"] == 9
    assert cell.config["published"] == {"num_hidden_layers": 40,
                                        "num_local_experts": 72}
    # every key the reference maps is applied to what runs, and the slot
    # bytes stated are checked against the program's
    conf = copy.deepcopy(cell.config)
    conf["model"]["shared_intermediate_size"] = 1024
    assert common.arch_config(conf, cell.reference).d_shared == 1024
    conf["kv_pool"]["state_bytes_per_lane"] += 2
    with pytest.raises(common.BenchError, match="state bytes"):
        common.arch_config(conf, cell.reference)


def test_param_count_at_the_published_sizes():
    """32B-A9B: the shared expert at its own width, counted once; a held
    share counts its own experts."""
    cfg = get_config("granite-4.0-h-small")
    assert cfg.param_count() == pytest.approx(32.2e9, rel=0.01)
    assert cfg.param_count(active_only=True) == pytest.approx(8.8e9,
                                                              rel=0.01)
    stage = replace(cfg, n_layers=10, experts_held=9)
    shapes = jax.eval_shape(get_model(stage).init, KEY)
    assert stage.param_count() == sum(a.size for a in
                                      jax.tree.leaves(shapes))


def test_launch_serve_runs_granite_through_the_paged_engine(monkeypatch,
                                                            capsys):
    from repro.launch import serve
    from repro.serve import PagedServeEngine
    built = []
    monkeypatch.setattr(serve, "PagedServeEngine",
                        lambda *a, **k: built.append(1)
                        or PagedServeEngine(*a, **k))
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "granite-4.0-h-small", "--reduced", "--paged",
        "--batch", "3", "--max-batch", "2", "--prompt-len", "20",
        "--new-tokens", "4", "--prefill-chunk", "8", "--block-size", "8"])
    serve.main()
    out = capsys.readouterr().out
    assert built and "generated: 3 requests, 12 tokens" in out


def test_engine_step_spans_carry_the_expert_counts():
    """Traced, each engine step's span carries the rows its held experts
    received, read at the syncs the step already has."""
    from repro.serve import PagedServeEngine
    cfg = reduced(get_config("granite-4.0-h-small"))
    params = get_model(cfg).init(KEY)
    eng = PagedServeEngine(cfg, params, block_size=8, max_batch=2,
                           max_len=40, prefill_chunk=8)
    obs.enable()
    try:
        eng.generate([[5] * 11, [9] * 3], max_new_tokens=3, warmup=False)
        steps = [e["args"] for e in obs.get_recorder().events()
                 if e["name"] == "engine_step" and e.get("ph") == "X"]
    finally:
        obs.enable(False)
    per_token = cfg.top_k * cfg.n_layers             # every expert held
    assert steps and all("expert_rows" in a for a in steps)
    assert sum(a["expert_rows"] for a in steps) == per_token * sum(
        a["prefill_tokens"] + a["lanes"] for a in steps)
