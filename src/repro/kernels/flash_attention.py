"""Flash attention as a Pallas TPU kernel (the paper's §3.1 "manually
implemented well-optimized big operation", adapted to the MXU/VMEM).

Design (TPU-native, not a CUDA port):
  * grid = (B, H, nQ, nK); the nK axis is "arbitrary" (sequential) so the
    online-softmax state (m, l, acc) lives in VMEM scratch across k-blocks;
  * the kernel works in a heads-major ``(B, H, S, hd)`` layout (the
    wrapper transposes the ``(B, S, H, hd)`` arguments), so a block's last
    two dims are ``(block, head_dim)``: the TPU tiles those two dims, and
    a single head in the second-minor dim would not be a legal tile;
  * q/k/v blocks are staged HBM->VMEM by BlockSpecs; block shapes default
    to (128, head_dim) — MXU-aligned (multiples of 128 on the matmul dims);
  * GQA: the k/v BlockSpec index_map folds the query head onto its kv head
    (h // group), so no repeated-KV materialization;
  * causal/sliding-window masking and gemma-style logit soft-capping are
    fused into the score block;
  * accumulation in f32, outputs cast back to the input dtype.

Ring-attention reuse (DESIGN.md §8): the online-softmax state can cross
kernel invocations.  ``carry=(m, l, acc)`` seeds the scratch instead of
the (-inf, 0, 0) init, ``return_carry=True`` returns the *unnormalized*
state instead of the normalized output, and ``kv_offset`` shifts the key
positions seen by the causal/window mask (the keys of a rotated ring
chunk live at a different absolute offset than their local indices).
A full pass equals a chain of per-chunk passes::

    st = flash_attention(q, k0, v0, return_carry=True)
    st = flash_attention(q, k1, v1, carry=st, kv_offset=S0,
                         return_carry=True)
    out, lse = flash_carry_finalize(st, q.dtype)

which is exactly the per-ring-step contract ``dist/ring.py`` relies on —
the kernel body is unchanged between the two modes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(*refs, scale, causal, window, softcap, q_offset, kv_offset,
                  kv_len, block_q, block_k, n_k, has_carry, return_carry):
    """One (b, h, qi, ki) grid step.

    ``refs`` layout depends on the mode:
      inputs:  q, k, v [, m_in, l_in, acc_in when has_carry]
      outputs: o                  (return_carry=False)
               m_out, l_out, acc_out   (return_carry=True)
      scratch: m_scr, l_scr, acc_scr
    """
    q_ref, k_ref, v_ref = refs[0], refs[1], refs[2]
    pos = 3
    carry_refs = None
    if has_carry:
        carry_refs = refs[pos:pos + 3]
        pos += 3
    out_refs = refs[pos:-3]
    m_scr, l_scr, acc_scr = refs[-3:]

    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        if has_carry:
            m_in, l_in, acc_in = carry_refs
            m_scr[...] = m_in[0, 0]
            l_scr[...] = l_in[0, 0]
            acc_scr[...] = acc_in[0, 0]
        else:
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap

    qi = pl.program_id(2)
    qpos = (qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
            + q_offset)
    # local key index (masks chunk padding via kv_len) vs global key
    # position (masks causality/window; a ring chunk's keys sit kv_offset
    # tokens into the global sequence)
    kidx = (ki * block_k
            + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1))
    kpos = kidx + kv_offset
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    if kv_len is not None:
        mask &= kidx < kv_len
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                 # (bq, 1)
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                              # (bq, bk)
    # a fully-masked block with a still -inf running max would exp(0)=1:
    # re-zero the masked lanes explicitly (cheap, and carry-safe)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)                      # (bq, 1)
    l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_k - 1)
    def _done():
        if return_carry:
            m_out, l_out, acc_out = out_refs
            m_out[0, 0] = m_scr[...]
            l_out[0, 0] = l_scr[...]
            acc_out[0, 0] = acc_scr[...]
        else:
            (o_ref,) = out_refs
            l = l_scr[...]
            l = jnp.where(l == 0.0, 1.0, l)             # fully-masked rows
            o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_carry_init(B, Sq, H, hd):
    """Neutral online-softmax state: (m, l, acc) = (-inf, 0, 0), f32.

    Shapes: m, l (B, Sq, H, 1); acc (B, Sq, H, hd) — the q-block layout the
    kernel's carry BlockSpecs expect."""
    return (jnp.full((B, Sq, H, 1), NEG_INF, jnp.float32),
            jnp.zeros((B, Sq, H, 1), jnp.float32),
            jnp.zeros((B, Sq, H, hd), jnp.float32))


def flash_carry_finalize(carry, dtype=None):
    """Normalize an accumulated carry: returns (out, lse).

    ``out = acc / l`` cast to ``dtype`` (default: keep f32); ``lse = m +
    log l`` is the log-sum-exp the flash backward recomputes probs from.
    Fully-masked rows produce out = 0, lse = NEG_INF."""
    m, l, acc = carry
    safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / safe
    if dtype is not None:
        out = out.astype(dtype)
    lse = jnp.where(l[..., 0] == 0.0, NEG_INF, m[..., 0] + jnp.log(safe[..., 0]))
    return out, lse


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    q_offset=0, kv_offset=0, kv_len=None, carry=None,
                    return_carry=False, block_q=128, block_k=128,
                    interpret=None):
    """q: (B, Sq, H, hd); k/v: (B, Sk, K, hd). Returns (B, Sq, H, hd) —
    or, with ``return_carry=True``, the unnormalized ``(m, l, acc)`` state
    (finalize with :func:`flash_carry_finalize`).  ``carry`` seeds the
    state from a previous chunk's output; ``kv_offset`` is the absolute
    position of k[:, 0] (ring chunks)."""
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    assert H % K == 0
    G = H // K
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Sk, 8))
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    if pk and kv_len is None:
        kv_len = Sk                       # mask the padded keys
    if pq:
        q = jnp.pad(q, [(0, 0), (0, pq), (0, 0), (0, 0)])
    if pk:
        k = jnp.pad(k, [(0, 0), (0, pk), (0, 0), (0, 0)])
        v = jnp.pad(v, [(0, 0), (0, pk), (0, 0), (0, 0)])
    Sq_p, Sk_p = Sq + pq, Sk + pk
    n_q, n_k = Sq_p // block_q, Sk_p // block_k

    has_carry = carry is not None
    if has_carry:
        m0, l0, acc0 = carry
        assert m0.shape == (B, Sq, H, 1) and acc0.shape == (B, Sq, H, hd), \
            (m0.shape, acc0.shape)
        if pq:  # padded q rows carry the neutral state
            m0 = jnp.pad(m0, [(0, 0), (0, pq), (0, 0), (0, 0)],
                         constant_values=NEG_INF)
            l0 = jnp.pad(l0, [(0, 0), (0, pq), (0, 0), (0, 0)])
            acc0 = jnp.pad(acc0, [(0, 0), (0, pq), (0, 0), (0, 0)])

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(hd), causal=causal,
        window=window, softcap=softcap, q_offset=q_offset,
        kv_offset=kv_offset, kv_len=kv_len, block_q=block_q, block_k=block_k,
        n_k=n_k, has_carry=has_carry, return_carry=return_carry)

    heads_major = lambda x: jnp.swapaxes(x, 1, 2)      # (B,S,H,.)<->(B,H,S,.)
    q_spec = pl.BlockSpec((1, 1, block_q, hd),
                          lambda b, h, qi, ki: (b, h, qi, 0))
    scalar_spec = pl.BlockSpec((1, 1, block_q, 1),
                               lambda b, h, qi, ki: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, hd),
                           lambda b, h, qi, ki, G=G: (b, h // G, ki, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    inputs = [heads_major(q), heads_major(k), heads_major(v)]
    if has_carry:
        in_specs += [scalar_spec, scalar_spec, q_spec]
        inputs += [heads_major(m0), heads_major(l0), heads_major(acc0)]

    if return_carry:
        out_specs = [scalar_spec, scalar_spec, q_spec]
        out_shape = [jax.ShapeDtypeStruct((B, H, Sq_p, 1), jnp.float32),
                     jax.ShapeDtypeStruct((B, H, Sq_p, 1), jnp.float32),
                     jax.ShapeDtypeStruct((B, H, Sq_p, hd), jnp.float32)]
    else:
        out_specs = [q_spec]
        out_shape = [jax.ShapeDtypeStruct((B, H, Sq_p, hd), q.dtype)]

    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, hd), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(*inputs)

    out = [heads_major(x) for x in out]
    if return_carry:
        m, l, acc = out
        if pq:
            m, l, acc = m[:, :Sq], l[:, :Sq], acc[:, :Sq]
        return m, l, acc
    (o,) = out
    if pq:
        o = o[:, :Sq]
    return o
