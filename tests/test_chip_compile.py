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
    pool = (2, nb, BLOCK, K * HD)                # two stacked layers
    args = [spec((LANES, H, HD), jnp.bfloat16), spec(pool, kd),
            spec(pool, kd), spec((LANES, PAGES), jnp.int32),
            spec((LANES,), jnp.int32), spec((), jnp.int32)]
    kw = dict(_defaults("paged_attention"), interpret=False)
    if kd == jnp.int8:
        args += [spec((2, nb, BLOCK, K), jnp.float32)] * 2
        f = lambda q, k, v, t, n, l, ks, vs: paged_attention(
            q, k, v, t, n, l, k_scale=ks, v_scale=vs, **kw)
    else:
        f = lambda q, k, v, t, n, l: paged_attention(q, k, v, t, n, l, **kw)
    _compile(f, *args, kernel="paged_attention")


def _paged_step_args(spec, model, blocks, lanes, pages):
    place = lambda tree: jax.tree.map(lambda s: spec(s.shape, s.dtype), tree)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        lambda: model.make_paged_cache(blocks, BLOCK, lanes)))
    return params, cache


def test_paged_decode_step_compiles_with_kernel(spec, monkeypatch):
    """The serving decode step, at full width (two layers), takes the
    kernel branch the model picks on a TPU and compiles for the chip."""
    from dataclasses import replace
    from repro.models import get_model
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = get_model(replace(CFG, n_layers=2))
    params, cache = _paged_step_args(spec, model, LANES * PAGES + 1, LANES,
                                     PAGES)
    batch = {"tokens": spec((LANES, 1), jnp.int32),
             "block_tables": spec((LANES, PAGES), jnp.int32),
             "pos": spec((LANES,), jnp.int32),
             "active": spec((LANES,), jnp.bool_)}
    _compile(model.decode_paged, params, cache, batch,
             kernel="paged_attention")


_POOL_OPS = re.compile(r"= \w+\[([\d,]*)\]\S* "
                       r"(copy|copy-start|dynamic-slice|dynamic-update-slice)\(")


@pytest.mark.parametrize("arch,layers,blocks,lanes", [
    ("qwen1.5-0.5b", 24, 3840, 64),    # the chat cell: K 16, hd 64
    ("starcoder2-15b", 10, 8193, 32),  # the backlog cell: K 4, hd 128
])
def test_paged_steps_update_pool_in_place(spec, monkeypatch, arch, layers,
                                          blocks, lanes):
    """Both serving steps, as a serving cell runs them (its layers under
    the layer scan, its whole pool), update the donated stacked pool in
    place: no copy, slice or update-slice of an array holding the pool's
    block count anywhere in the compiled program, the pool aliased from
    input to output, and temporaries under a quarter of the pool.  A
    (bs, K, hd) pool tile made XLA copy each layer's pool out, re-lay it
    out for the kernel and back, and write it into a second stacked pool;
    at 24 layers its temporaries outgrew the pool.  The scan keeps each
    compile to a few seconds."""
    from dataclasses import replace
    from repro.models import get_model
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pages, chunk = 256, 512
    model = get_model(replace(get_config(arch), n_layers=layers))
    params, cache = _paged_step_args(spec, model, blocks, lanes, pages)
    pool = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    i32 = lambda *shape: spec(shape, jnp.int32)
    steps = {
        "decode_paged": {"tokens": i32(lanes, 1),
                         "block_tables": i32(lanes, pages), "pos": i32(lanes),
                         "active": spec((lanes,), jnp.bool_)},
        "prefill_chunk_paged": {"tokens": i32(1, chunk),
                                "block_tables": i32(1, pages), "start": i32(),
                                "length": i32(), "slot": i32()}}
    for name, batch in steps.items():
        compiled = jax.jit(getattr(model, name), donate_argnums=(1,)).lower(
            params, cache, batch).compile()
        whole = [m.group(0) for m in _POOL_OPS.finditer(compiled.as_text())
                 if str(blocks) in m.group(1).split(",")]
        assert not whole, (name, whole[:4])
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= pool, (name, mem)
        assert mem.temp_size_in_bytes < pool / 4, (name, mem)


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


def test_granite_steps_update_states_in_place(spec, monkeypatch):
    """granite-4.0-h-small's serving steps as its cell runs them (one
    stage of 10 layers, 9 held experts, 64 lanes, the whole pool): the
    Mamba layers' slot states ride the layer scan's carry and are updated
    in place.  A decode step's temporaries stay under one layer's states
    (268 MB at 64 lanes), where riding the scan as xs/ys made a second
    copy of all of them (2.45 GB); a prefill chunk's hold no copy of them
    either (its own f32 context scores are 0.54 GB).  The cache is
    aliased from input to output, and the grouped expert matmul compiles
    as the chip's kernel."""
    from bench import common
    from repro.models import get_model
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = common.load_cell("granite-4.0-h-small-pp4-ep8.rag-chat")
    cfg = common.arch_config(cell.config, cell.reference)
    eng, nb = cell.config["engine"], cell.config["kv_pool"]["num_blocks"]
    lanes, chunk = eng["max_batch"], eng["prefill_chunk"]
    pages = eng["max_len"] // eng["block_size"]
    model = get_model(cfg)
    place = lambda tree: jax.tree.map(lambda s: spec(s.shape, s.dtype), tree)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        lambda: model.make_paged_cache(nb, eng["block_size"], lanes)))
    size = sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(cache["layers"]))
    states = lanes * cell.config["kv_pool"]["state_bytes_per_lane"]
    i32 = lambda *shape: spec(shape, jnp.int32)
    steps = {
        "decode_paged": ({"tokens": i32(lanes, 1),
                          "block_tables": i32(lanes, pages), "pos": i32(lanes),
                          "active": spec((lanes,), jnp.bool_)}, 300e6),
        "prefill_chunk_paged": ({"tokens": i32(1, chunk),
                                 "block_tables": i32(1, pages),
                                 "start": i32(), "length": i32(),
                                 "slot": i32()}, 0.3 * states)}
    for name, (batch, most) in steps.items():
        compiled = jax.jit(getattr(model, name), donate_argnums=(1,)).lower(
            params, cache, batch).compile()
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= size, (name, mem)
        assert mem.temp_size_in_bytes < most, (name, mem)
        assert re.search(r"%expert_gmm(\.\d+)? = .*tpu_custom_call",
                         compiled.as_text()), name


def test_hybrid_states_stay_in_place_under_the_layer_scan(spec,
                                                          monkeypatch):
    """Five of granite's periods (50 layers, run under ``lax.scan``) at a
    narrower width, 64 lanes: both serving steps keep their temporaries
    under a tenth of the slot states.  States riding the scan as xs/ys
    were written out as a second stacked copy of all of them each step
    (3.1 GB of decode temporaries for 3.06 GB of states)."""
    from dataclasses import replace
    from repro.models import get_model
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = replace(get_config("granite-4.0-h-small"), n_layers=50,
                  d_model=1024, n_heads=8, n_kv_heads=2, ssm_heads=32,
                  vocab=32768, experts_held=9)
    lanes, pages, chunk = 64, 64, 512
    model = get_model(cfg)
    place = lambda tree: jax.tree.map(lambda s: spec(s.shape, s.dtype), tree)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        lambda: model.make_paged_cache(lanes * pages + 1, BLOCK, lanes)))
    states = sum(a.size * a.dtype.itemsize
                 for layer in cache["layers"].values() if "ssm" in layer
                 for a in jax.tree.leaves(layer))
    i32 = lambda *shape: spec(shape, jnp.int32)
    steps = {
        "decode_paged": {"tokens": i32(lanes, 1),
                         "block_tables": i32(lanes, pages), "pos": i32(lanes),
                         "active": spec((lanes,), jnp.bool_)},
        "prefill_chunk_paged": {"tokens": i32(1, chunk),
                                "block_tables": i32(1, pages), "start": i32(),
                                "length": i32(), "slot": i32()}}
    for name, batch in steps.items():
        mem = jax.jit(getattr(model, name), donate_argnums=(1,)).lower(
            params, cache, batch).compile().memory_analysis()
        assert mem.temp_size_in_bytes < states / 10, (name, mem)
