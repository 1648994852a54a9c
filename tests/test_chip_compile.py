"""The main-path Pallas kernels compile for a TPU v5e at qwen1.5-0.5b
widths, with their registry-default schedules.

The TPU compiler is asked ahead of time, for a described ``v5e:2x2``
topology: no chip is attached, nothing runs.  It refuses what interpret
mode accepts — a block whose last two dims are not whole or aligned to the
(8, 128) tiling, more scoped VMEM than a kernel may take — so these tests
guard the chip path of every later change at no chip time.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around the compiles, since
an entry written without a chip cannot be read back.
"""
from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import registry
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_update import sgd_momentum
from repro.kernels.paged_attention import paged_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.sampling import sample_tokens

CFG = get_config("qwen1.5-0.5b")
H, K, HD = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
BLOCK, PAGES, LANES = 16, 33, 8          # 528-token lanes, 8 decode lanes


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: an argument placed on one described chip."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(f, *args, kernel=None):
    """Compile for the described chip; the kernel must survive as a
    Mosaic custom call (not a fallback lowered by XLA), under the name
    ``kernel`` its ``pallas_call`` gives it when one is asked for: the
    device trace's readers find it by that name."""
    compiled = jax.jit(f).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if kernel is not None:
        assert re.search(rf"%{kernel}(\.\d+)? = .*tpu_custom_call", text), \
            f"no tpu_custom_call named {kernel}"
    return compiled


def _defaults(op):
    return dict(registry.get(op).defaults)


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_decode_compiles(spec, kv_dtype):
    nb = LANES * PAGES + 1
    kd = jnp.dtype(kv_dtype)
    args = [spec((LANES, H, HD), jnp.bfloat16),
            spec((nb, BLOCK, K, HD), kd), spec((nb, BLOCK, K, HD), kd),
            spec((LANES, PAGES), jnp.int32), spec((LANES,), jnp.int32)]
    kw = dict(_defaults("paged_attention"), interpret=False)
    if kd == jnp.int8:
        args += [spec((nb, BLOCK, K), jnp.float32)] * 2
        f = lambda q, k, v, t, n, ks, vs: paged_attention(
            q, k, v, t, n, k_scale=ks, v_scale=vs, **kw)
    else:
        f = lambda q, k, v, t, n: paged_attention(q, k, v, t, n, **kw)
    _compile(f, *args, kernel="paged_attention")


def test_paged_decode_step_compiles_with_kernel(spec, monkeypatch):
    """The serving decode step, at full width (two layers), takes the
    kernel branch the model picks on a TPU and compiles for the chip."""
    from dataclasses import replace
    from repro.models import get_model
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = get_model(replace(CFG, n_layers=2))
    place = lambda tree: jax.tree.map(lambda s: spec(s.shape, s.dtype), tree)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        lambda: model.make_paged_cache(LANES * PAGES + 1, BLOCK, LANES)))
    batch = {"tokens": spec((LANES, 1), jnp.int32),
             "block_tables": spec((LANES, PAGES), jnp.int32),
             "pos": spec((LANES,), jnp.int32),
             "active": spec((LANES,), jnp.bool_)}
    _compile(model.decode_paged, params, cache, batch,
             kernel="paged_attention")


def test_flash_attention_compiles(spec):
    qkv = [spec((2, 512, H, HD), jnp.bfloat16)] * 3
    kw = dict(_defaults("flash_attention"), interpret=False)
    _compile(lambda q, k, v: flash_attention(q, k, v, **kw), *qkv,
             kernel="flash_attention")


def test_rmsnorm_compiles(spec):
    kw = dict(_defaults("rmsnorm"), interpret=False)
    _compile(lambda x, w: rmsnorm(x, w, **kw),
             spec((2048, CFG.d_model), jnp.bfloat16),
             spec((CFG.d_model,), jnp.bfloat16), kernel="rmsnorm")


@pytest.mark.parametrize("rows", [1, LANES])   # first token, decode batch
def test_sampling_compiles(spec, rows):
    kw = dict(_defaults("sample_tokens"), temperature=0.8, top_k=50,
              top_p=0.9, interpret=False)
    _compile(functools.partial(sample_tokens, **kw),
             spec((rows, CFG.vocab), jnp.float32),
             spec((rows,), jnp.float32), kernel="sample_tokens")


def test_fused_sgd_update_compiles(spec):
    kw = dict(_defaults("sgd_momentum"), interpret=False)
    shape = (CFG.d_model, CFG.d_ff)
    _compile(functools.partial(sgd_momentum, **kw),
             spec(shape, jnp.bfloat16), spec(shape, jnp.bfloat16),
             spec(shape, jnp.float32), kernel="sgd_momentum")


def test_sampler_program_name(spec):
    """The serving engine samples through ``kernels.ops``, whose jitted
    sampler compiles to its own program: the device trace names it
    ``jit_sample_tokens``, which the chat cell's sampler reader matches."""
    from repro.kernels import ops
    kw = dict(_defaults("sample_tokens"), temperature=0.8, top_k=50,
              top_p=0.9, interpret=False)
    compiled = ops._sample_jit.lower(
        spec((LANES, CFG.vocab), jnp.float32), spec((LANES,), jnp.float32),
        **kw).compile()
    assert compiled.as_text().startswith("HloModule jit_sample_tokens,")
