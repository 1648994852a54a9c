"""Per-architecture smoke tests (deliverable f).

Each assigned arch instantiates a REDUCED same-family variant (<=2
super-blocks, d_model<=512, <=4 experts) and runs one forward + one
training step on CPU, asserting output shapes and absence of NaNs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, LONG_CONTEXT_ARCHS, get_config
from repro.models import get_model, reduced

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def models():
    return {}


def _reduced_model(arch):
    cfg = reduced(get_config(arch))
    return get_model(cfg)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_loss_finite(arch):
    m = _reduced_model(arch)
    params = m.init(KEY)
    batch = m.make_batch(KEY, "train", 2, 32)
    loss, metrics = jax.jit(m.loss)(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), (arch, float(loss))
    assert np.isfinite(float(metrics["ce"]))
    # fresh init => CE near ln(V)
    assert abs(float(metrics["ce"]) - np.log(m.cfg.vocab)) < 1.5


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_improves(arch):
    """One SGD step must run and produce finite, changed params."""
    m = _reduced_model(arch)
    params = m.init(KEY)
    batch = m.make_batch(KEY, "train", 2, 32)

    @jax.jit
    def step(p, b):
        (l, _), g = jax.value_and_grad(m.loss, has_aux=True)(p, b)
        p2 = jax.tree.map(lambda w, gw: w - 0.002 * gw.astype(w.dtype), p, g)
        return l, p2

    l0, params1 = step(params, batch)
    l1, _ = step(params1, batch)
    assert np.isfinite(float(l0)) and np.isfinite(float(l1))
    assert float(l1) < float(l0) + 1e-3, (arch, float(l0), float(l1))
    leaves0, leaves1 = jax.tree.leaves(params), jax.tree.leaves(params1)
    assert any(not np.allclose(a, b) for a, b in zip(leaves0, leaves1))
    for leaf in leaves1:
        assert np.all(np.isfinite(np.asarray(leaf, np.float32)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_shapes(arch):
    m = _reduced_model(arch)
    cfg = m.cfg
    params = m.init(KEY)
    B, S = 2, 16
    batch = m.make_batch(KEY, "prefill", B, S)
    logits, cache = jax.jit(lambda p, b: m.prefill(p, b, pad_to=S + 8))(
        params, batch)
    assert logits.shape == (B, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits)))
    tok = jnp.zeros((B, 1), jnp.int32)
    logits2, cache2 = jax.jit(m.decode)(params, cache, {"tokens": tok})
    assert logits2.shape == (B, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits2)))
    assert int(cache2["pos"]) == int(cache["pos"]) + 1


@pytest.mark.parametrize("arch", sorted(LONG_CONTEXT_ARCHS))
def test_long_context_variant_exists(arch):
    cfg = get_config(arch, long_context=True)
    for spec in cfg.pattern:
        if spec.kind == "attn":
            assert spec.window is not None  # sub-quadratic for long_500k


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_dims_match_assignment(arch):
    """The FULL configs carry the exact assigned dimensions."""
    expect = {
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "internvl2-76b": (80, 8192, 64, 8, 28672, 128256),
        "qwen1.5-0.5b": (24, 1024, 16, 16, 2816, 151936),
        "gemma2-2b": (26, 2304, 8, 4, 9216, 256000),
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "whisper-base": (6, 512, 8, 8, 2048, 51865),
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202048),
        "starcoder2-15b": (40, 6144, 48, 4, 24576, 49152),
        "mamba2-130m": (24, 768, 1, 1, 0, 50280),
        "granite-20b": (52, 6144, 48, 1, 24576, 49152),
        "granite-4.0-h-small": (40, 4096, 32, 8, 768, 100352),
    }[arch]
    c = get_config(arch)
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == expect


def test_param_counts_near_nameplate():
    """param_count() should land near each model's nameplate size."""
    expect = {"dbrx-132b": 132e9, "internvl2-76b": 70e9,
              "qwen1.5-0.5b": 0.46e9, "gemma2-2b": 2.6e9,
              "jamba-1.5-large-398b": 398e9, "whisper-base": 74e6,
              "llama4-scout-17b-a16e": 108e9, "starcoder2-15b": 15e9,
              "mamba2-130m": 130e6, "granite-20b": 20e9,
              "granite-4.0-h-small": 32.2e9}
    for arch, target in expect.items():
        n = get_config(arch).param_count()
        assert 0.6 * target < n < 1.45 * target, (arch, n, target)


def test_moe_active_params_smaller():
    for arch in ("dbrx-132b", "llama4-scout-17b-a16e",
                 "jamba-1.5-large-398b", "granite-4.0-h-small"):
        c = get_config(arch)
        assert c.param_count(active_only=True) < 0.55 * c.param_count()
