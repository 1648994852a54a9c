"""The plain reference against the program at ``reduced()`` size on the
CPU: chunked paged prefill then paged decode against the reference's
full forward, and the Trainer's loss and gradients against the
reference's, for both configurations' layer kinds."""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common
from bench.reference import decoder
from bench.tests import tiny

common.use_src_path()

CONFIGS = ["qwen1.5-0.5b", "starcoder2-15b-pp4"]


def _f32(conf_name):
    conf = copy.deepcopy(common.load_json(
        common.BENCH / "configs" / f"{conf_name}.json"))
    conf["model"].update(tiny.SMALL, dtype="float32")
    conf["reduced"] = sorted(tiny.SMALL) + ["dtype"]
    m = conf["model"]
    conf["kv_pool"]["bytes_per_token"] = (
        m["num_hidden_layers"] * 2 * m["num_key_value_heads"]
        * m["head_dim"] * 4)
    d = decoder.dims_of(conf)
    return conf, common.arch_config(conf, decoder), d


@pytest.mark.parametrize("name", CONFIGS)
def test_paged_prefill_then_decode_matches_reference(name):
    from repro.models import get_model
    conf, cfg, d = _f32(name)
    model = get_model(cfg)
    params = common.make_params(cfg, 3, decoder, d)
    bs, P, C = 16, 8, 16
    cache = model.make_paged_cache(1 + P, bs, 1)
    table = jnp.arange(1, P + 1, dtype=jnp.int32)[None]
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg.vocab, 37).astype(np.int32)
    got = []
    for start in range(0, len(prompt), C):
        chunk = prompt[start:start + C]
        toks = np.zeros((1, C), np.int32)
        toks[0, :len(chunk)] = chunk
        logits, cache = model.prefill_chunk_paged(params, cache, {
            "tokens": jnp.asarray(toks), "block_tables": table,
            "start": jnp.asarray(start, jnp.int32),
            "length": jnp.asarray(len(chunk), jnp.int32),
            "slot": jnp.asarray(0, jnp.int32)})
    got.append(logits[0])
    seq = list(prompt)
    for _ in range(6):
        nxt = int(jnp.argmax(got[-1]))
        pos = len(seq)
        seq.append(nxt)
        logits, cache = model.decode_paged(params, cache, {
            "tokens": jnp.asarray([[nxt]], jnp.int32), "block_tables": table,
            "pos": jnp.asarray([pos], jnp.int32),
            "active": jnp.asarray([True])})
        got.append(logits[0])
    rows = jnp.arange(len(prompt) - 1, len(seq))
    want = decoder.logits_at(params, jnp.asarray(seq, jnp.int32), rows, d)
    np.testing.assert_allclose(np.stack(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", CONFIGS)
def test_trainer_loss_and_gradients_match_reference(name):
    from repro.models import get_model
    conf, cfg, d = _f32(name)
    model = get_model(cfg)
    params = common.make_params(cfg, 5, decoder, d)
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab, (2, 48)), jnp.int32)
    (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(
        params, {"tokens": toks})
    ref_loss, ref_grads = decoder.loss_and_grad(params, toks, d)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(ref_grads)[0]
    mine = dict(jax.tree_util.tree_flatten_with_path(grads)[0])
    for path, ref in flat:
        np.testing.assert_allclose(np.asarray(mine[path]), np.asarray(ref),
                                   rtol=2e-3, atol=2e-6, err_msg=str(path))


def test_fp8_control_departs_from_the_reference():
    conf, cfg, d = _f32("qwen1.5-0.5b")
    params = common.make_params(cfg, 9, decoder, d)
    toks = jnp.asarray(np.random.default_rng(2).integers(1, cfg.vocab, 64),
                       jnp.int32)
    rows = jnp.arange(64)
    ref = decoder.logits_at(params, toks, rows, d)
    ctl = decoder.logits_at(params, toks, rows, d, quant="fp8")
    err = float(jnp.max(jnp.abs(ref - ctl)))
    assert err > 1e-2 * float(jnp.std(ref))
