"""Jit'd public wrappers for the Pallas kernels, registry-resolved.

On CPU containers the kernels execute with ``interpret=True`` (the kernel
body runs in Python per grid step) — correctness validation only; TPU is
the performance target.

Every wrapper resolves its schedule tunables through the kernel registry
(DESIGN.md §13) before entering jit: explicit caller kwargs win, then the
autotune cache's winner for this shape bucket, then the registered
defaults.  The tunables ride the inner ``jax.jit`` as static argnames, so
a new winner simply traces a new specialization.
"""
from __future__ import annotations


import jax

from . import registry
from .expert_gmm import expert_gmm as _gmm
from .flash_attention import flash_attention as _flash
from .fused_update import sgd_momentum as _sgd
from .paged_attention import paged_attention as _paged
from .rmsnorm import rmsnorm as _rmsnorm
from .sampling import sample_tokens as _sample

_flash_jit = jax.jit(_flash, static_argnames=(
    "causal", "window", "softcap", "q_offset", "kv_offset", "kv_len",
    "return_carry", "block_q", "block_k", "interpret"))

_paged_jit = jax.jit(_paged, static_argnames=(
    "window", "softcap", "scale", "pages_per_step", "head_tile",
    "interpret"))

_gmm_jit = jax.jit(_gmm, static_argnames=("block_rows", "block_ff",
                                           "interpret"))

_rmsnorm_jit = jax.jit(_rmsnorm, static_argnames=("eps", "block_rows",
                                                  "interpret"))

_sgd_jit = jax.jit(_sgd, static_argnames=("lr", "mu", "weight_decay",
                                          "block", "interpret"))

_sample_jit = jax.jit(_sample, static_argnames=(
    "temperature", "top_k", "top_p", "rows_per_step", "interpret"))


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    q_offset=0, kv_offset=0, kv_len=None, carry=None,
                    return_carry=False, block_q=None, block_k=None,
                    interpret=None):
    p = registry.resolve(
        "flash_attention", {"block_q": block_q, "block_k": block_k},
        registry.get("flash_attention").bucket_of(q, k, v))
    return _flash_jit(q, k, v, causal=causal, window=window,
                      softcap=softcap, q_offset=q_offset,
                      kv_offset=kv_offset, kv_len=kv_len, carry=carry,
                      return_carry=return_carry, interpret=interpret, **p)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, layer, *,
                    k_scale=None, v_scale=None, window=None, softcap=None,
                    scale=None, pages_per_step=None, head_tile=None,
                    interpret=None):
    p = registry.resolve(
        "paged_attention",
        {"pages_per_step": pages_per_step, "head_tile": head_tile},
        registry.get("paged_attention").bucket_of(
            q, k_pages, v_pages, block_tables, lengths, layer,
            k_scale=k_scale))
    return _paged_jit(q, k_pages, v_pages, block_tables, lengths, layer,
                      k_scale=k_scale, v_scale=v_scale, window=window,
                      softcap=softcap, scale=scale, interpret=interpret, **p)


def expert_gmm(x, wg, wu, wd, tile_group, n_tiles, *, block_rows,
               block_ff=None, interpret=None):
    p = registry.resolve("expert_gmm", {"block_ff": block_ff},
                         registry.get("expert_gmm").bucket_of(
                             x, wg, wu, wd, tile_group, n_tiles,
                             block_rows=block_rows))
    return _gmm_jit(x, wg, wu, wd, tile_group, n_tiles,
                    block_rows=block_rows, interpret=interpret, **p)


def rmsnorm(x, weight, eps=1e-6, block_rows=None, interpret=None):
    p = registry.resolve("rmsnorm", {"block_rows": block_rows},
                         registry.get("rmsnorm").bucket_of(x, weight))
    return _rmsnorm_jit(x, weight, eps=eps, interpret=interpret, **p)


def sgd_momentum(param, grad, mom, *, lr=1e-3, mu=0.9, weight_decay=1e-4,
                 block=None, interpret=None):
    p = registry.resolve("sgd_momentum", {"block": block},
                         registry.get("sgd_momentum").bucket_of(param, grad,
                                                                mom))
    return _sgd_jit(param, grad, mom, lr=lr, mu=mu,
                    weight_decay=weight_decay, interpret=interpret, **p)


def sample_tokens(logits, u, *, temperature=1.0, top_k=None, top_p=None,
                  rows_per_step=None, interpret=None):
    p = registry.resolve("sample_tokens", {"rows_per_step": rows_per_step},
                         registry.get("sample_tokens").bucket_of(logits, u))
    return _sample_jit(logits, u, temperature=float(temperature),
                      top_k=top_k, top_p=top_p, interpret=interpret, **p)
