"""Fused SGD-momentum parameter update as a Pallas TPU kernel — the
KVStore *updater* (MXNet §2.3) as a mutating big-op.

MXNet's engine schedules parameter updates as mutations of the parameter
array (§3.2); the JAX analogue is input/output buffer aliasing
(``input_output_aliases``): param and momentum are updated in place, one
fused VMEM pass instead of 5 HBM-roundtrip elementwise ops
(decay-add, scale, momentum-mul, add, subtract).

The flat arrays are viewed as ``(rows, LANES)`` and a grid step takes
``block // LANES`` rows (a multiple of 8, or all rows), the tile shape
the TPU accepts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 512

def _update_kernel(p_ref, g_ref, m_ref, po_ref, mo_ref, *, lr, mu, wd):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32) + wd * p
    m = mu * m_ref[...] + g
    po_ref[...] = (p - lr * m).astype(po_ref.dtype)
    mo_ref[...] = m


def sgd_momentum(param, grad, mom, *, lr=1e-3, mu=0.9, weight_decay=1e-4,
                 block=65536, interpret=None):
    """param: any shape (bf16/f32); grad: same shape; mom: f32 master.

    Returns (new_param, new_mom); buffers are aliased (donated) so the
    update is in place, like the engine's write-tag mutation.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    shape = param.shape
    n = param.size
    rows = -(-n // LANES)
    rb = max(8, block // LANES // 8 * 8)           # rows per grid step
    if rows <= rb:
        rb = rows                                  # one step, whole array
    rows += (-rows) % rb
    pad = rows * LANES - n
    p2, g2, m2 = (jnp.pad(a.reshape(-1), (0, pad)).reshape(rows, LANES)
                  for a in (param, grad, mom))

    spec = pl.BlockSpec((rb, LANES), lambda i: (i, 0))
    new_p, new_m = pl.pallas_call(
        functools.partial(_update_kernel, lr=lr, mu=mu, wd=weight_decay),
        grid=(rows // rb,),
        in_specs=[spec] * 3,
        out_specs=[spec] * 2,
        out_shape=[jax.ShapeDtypeStruct(p2.shape, param.dtype),
                   jax.ShapeDtypeStruct(m2.shape, jnp.float32)],
        input_output_aliases={0: 0, 2: 1},
        interpret=interpret,
    )(p2, g2, m2)
    new_p = new_p.reshape(-1)[:n].reshape(shape)
    new_m = new_m.reshape(-1)[:n].reshape(shape)
    return new_p, new_m
