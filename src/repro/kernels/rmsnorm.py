"""Fused RMSNorm as a Pallas TPU kernel.

One VMEM-staged pass: f32 mean-square, rsqrt, scale by (1 + w) — the
unfused jnp version reads x twice and materializes the f32 upcast in HBM.
Rows are tiled (block_rows, D); the weight block is broadcast (index_map
pins it to block 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)            # (rows, D)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    w = w_ref[...].astype(jnp.float32)
    o_ref[...] = (y * (1.0 + w)).astype(o_ref.dtype)


def rmsnorm(x, weight, eps=1e-6, block_rows=256, interpret=None):
    """x: (..., D); weight: (D,)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    orig_shape = x.shape
    D = x.shape[-1]
    xr = x.reshape(-1, D)
    n = xr.shape[0]
    block_rows = min(block_rows, n)
    pad = (-n) % block_rows
    if pad:
        xr = jnp.pad(xr, [(0, pad), (0, 0)])
    grid = (xr.shape[0] // block_rows,)

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xr.shape, x.dtype),
        name="rmsnorm",
        interpret=interpret,
    )(xr, weight.reshape(1, D))
    if pad:
        out = out[:n]
    return out.reshape(orig_shape)
