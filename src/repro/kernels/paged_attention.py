"""Paged decode attention as a Pallas TPU kernel.

One query row per sequence against a block-table-indirected KV pool
(continuous-batching decode, DESIGN.md §9).  Where the flash kernel
streams *contiguous* k-blocks, this kernel streams *logical pages*: the
grid's last axis walks a sequence's block table and the k/v BlockSpec
``index_map`` reads the physical block id out of a scalar-prefetched
table — the DMA engine gathers through the indirection, the MXU only
ever sees dense (block_size, head_dim) tiles.

Design notes (TPU-native, mirrors ``flash_attention.py``):

* grid = (B, K/head_tile, n_pages/pages_per_step); the page axis is
  "arbitrary" (sequential) so the online-softmax carry (m, l, acc) lives
  in VMEM scratch across pages;
* scalar prefetch: ``block_tables (B, n_pages)`` and ``lengths (B,)``
  ride ahead of the grid so index_maps can compute DMA source blocks
  (``pltpu.PrefetchScalarGridSpec``);
* GQA: each grid step processes ``head_tile`` KV heads with all their G
  query heads as the q tile (ht, G, hd) — no repeated-KV
  materialization;
* tunables (registry op ``paged_attention``): ``pages_per_step`` fetches
  several table entries per grid step (each page is its own BlockSpec
  input, so the DMA engine issues the gathers in parallel and the MXU
  sees one (ht, pps*bs, hd) tile); ``head_tile`` batches KV heads per
  step.  Both shrink grid-overhead-bound decode steps;
* quantized pools (DESIGN.md §13): when ``k_scale``/``v_scale``
  (num_blocks, block_size, K) f32 ride along, k/v tiles are stored
  int8/fp8 and dequantized *inside the score block* right after the DMA
  lands (``tile.astype(f32) * scale``) — no fp16 copy of the cache ever
  materializes;
* pages past a sequence's live length are skipped (``pl.when``), so a
  short sequence in a long-table batch costs only its own pages of MXU
  work (the DMA for the skipped block still lands — sink pages make it
  harmless);
* sliding-window layers mask ``kpos > qpos - window`` with qpos =
  length-1 (the paged pool is position-ordered, no ring buffer);
* accumulation in f32, output cast to the query dtype.

The online-softmax recurrence is shared with ``flash_attention.py``
(PR 3's carry form); only the page indirection differs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def legal_head_tile(head_tile, K: int, quant: bool) -> int:
    """KV heads per grid step.  The TPU tiles a block's last two dims
    (heads, head_dim) in (8, 128) units unless a dim is whole, so a tile
    is a multiple of 8 that divides K, or all K heads.  Quantized pools
    put the heads in the lane dim of their scale blocks, which then need
    all K.  Any other request falls back to K.

    >>> legal_head_tile(8, 16, False), legal_head_tile(8, 16, True)
    (8, 16)
    >>> legal_head_tile(16, 2, False), legal_head_tile(1, 16, False)
    (2, 16)
    """
    ht = int(head_tile)
    if quant or ht <= 0 or K % ht or (ht % 8 and ht != K):
        return K
    return ht


def _paged_kernel(tables_ref, lens_ref, q_ref, *refs, scale, block_size,
                  n_steps, pps, quant, window, softcap):
    """One (b, kv-head-tile, page-group) grid step."""
    k_refs = refs[:pps]
    v_refs = refs[pps:2 * pps]
    if quant:
        ks_refs = refs[2 * pps:3 * pps]
        vs_refs = refs[3 * pps:4 * pps]
        o_ref, m_scr, l_scr, acc_scr = refs[4 * pps:]
    else:
        o_ref, m_scr, l_scr, acc_scr = refs[2 * pps:]

    b = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lens_ref[b]                       # live tokens incl. current

    def tile(j, kv_ref, s_ref):
        """(1, bs, ht, hd) page -> dequantized f32 (ht, bs, hd)."""
        t = jnp.swapaxes(kv_ref[0], 0, 1).astype(jnp.float32)
        if quant:
            t = t * jnp.swapaxes(s_ref[0], 0, 1).astype(jnp.float32)[..., None]
        return t

    @pl.when(pi * pps * block_size < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                 # (ht, G, hd)
        k = jnp.concatenate(
            [tile(j, k_refs[j], ks_refs[j] if quant else None)
             for j in range(pps)], axis=1)               # (ht, pps*bs, hd)
        v = jnp.concatenate(
            [tile(j, v_refs[j], vs_refs[j] if quant else None)
             for j in range(pps)], axis=1)

        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:                          # (ht, G, pps*bs)
            s = jnp.tanh(s / softcap) * softcap

        kpos = (pi * pps * block_size
                + jax.lax.broadcasted_iota(jnp.int32, (1, 1, pps * block_size),
                                           2))
        mask = kpos < length
        if window is not None:
            # the single query row sits at absolute position length-1
            mask &= kpos > (length - 1) - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                              # (ht, G, 1)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)         # fully-masked block: exp(0)=1
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_prev + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(pi == n_steps - 1)
    def _done():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)                  # inactive lanes
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    k_scale=None, v_scale=None, window=None, softcap=None,
                    pages_per_step=1, head_tile=16, interpret=None):
    """Single-token attention through a paged KV pool.

    q: (B, H, hd) — the current token's query rows;
    k_pages/v_pages: (num_blocks, block_size, K, hd) physical pools;
    block_tables: (B, n_pages) int32, logical page -> physical block
    (sink-filled past each sequence's pages);
    lengths: (B,) int32 — live tokens per sequence INCLUDING the current
    one (the row at position lengths-1 must already be written);
    k_scale/v_scale: (num_blocks, block_size, K) f32 per-row scales when
    the pools are quantized (both or neither);
    pages_per_step / head_tile: grid tunables (see module docstring) —
    pure schedule knobs, the output is bitwise independent of them up to
    f32 summation order; ``head_tile`` goes through
    :func:`legal_head_tile`.

    Returns (B, H, hd).  Lanes with length 0 return zeros.
    """
    B, H, hd = q.shape
    NB, bs, K, _ = k_pages.shape
    assert H % K == 0, (H, K)
    assert (k_scale is None) == (v_scale is None)
    quant = k_scale is not None
    G = H // K
    n_pages = block_tables.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    ht = legal_head_tile(head_tile, K, quant)
    pps = max(1, min(int(pages_per_step), n_pages))
    pad = (-n_pages) % pps
    tables = block_tables.astype(jnp.int32)
    if pad:
        # pad the table to a pps multiple with sink pages (block 0); the
        # pad pages sit past every live length, so they are masked out
        tables = jnp.pad(tables, [(0, 0), (0, pad)])
    n_steps = (n_pages + pad) // pps

    qg = q.reshape(B, K, G, hd)
    kernel = functools.partial(
        _paged_kernel, scale=1.0 / math.sqrt(hd), block_size=bs,
        n_steps=n_steps, pps=pps, quant=quant, window=window,
        softcap=softcap)

    q_spec = pl.BlockSpec((1, ht, G, hd), lambda b, kh, pi, *_: (b, kh, 0, 0))

    def kv_spec(j):
        return pl.BlockSpec(
            (1, bs, ht, hd),
            lambda b, kh, pi, tables, lens: (tables[b, pi * pps + j], 0,
                                             kh, 0))

    def scale_spec(j):
        return pl.BlockSpec(
            (1, bs, ht),
            lambda b, kh, pi, tables, lens: (tables[b, pi * pps + j], 0, kh))

    in_specs = ([q_spec]
                + [kv_spec(j) for j in range(pps)]
                + [kv_spec(j) for j in range(pps)])
    inputs = [qg] + [k_pages] * pps + [v_pages] * pps
    if quant:
        in_specs += ([scale_spec(j) for j in range(pps)]
                     + [scale_spec(j) for j in range(pps)])
        inputs += [k_scale] * pps + [v_scale] * pps

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K // ht, n_steps),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((ht, G, 1), jnp.float32),     # running max m
            pltpu.VMEM((ht, G, 1), jnp.float32),     # running sum l
            pltpu.VMEM((ht, G, hd), jnp.float32),    # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(tables, lengths.astype(jnp.int32), *inputs)
    return out.reshape(B, H, hd)
