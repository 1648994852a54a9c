"""Fused top-k / top-p token sampling as a Pallas kernel.

Per decode step the serve engines need one token per lane from the
``(B, V)`` logits.  The host path is a sort (top-k), a cumsum (top-p) and
a categorical draw — three full-vocab passes with HBM round-trips between
them.  This kernel fuses filter + softmax + inverse-CDF draw into one
VMEM-resident pass per row tile; the only inputs besides logits are B
uniform floats (drawn with ``jax.random`` outside — the kernel itself is
RNG-free and deterministic).

Neither sort nor cumsum lowers on the TPU's vector unit, so every
search is a bisection over masked sums.  Both cutoffs are found by a
32-step binary search over the *bit space* of the score values: an IEEE
f32 compares like its sign-adjusted uint32 image, so "the k-th largest
score" and "the smallest score whose strictly-greater probability mass is
< top_p * Z" are both exact lattice points reachable by monotone
predicate bisection (no float epsilon anywhere — ties share one key and
are kept or dropped together, matching ``ref.sample_ref``).

Semantics (shared with the oracle):

* temperature == 0: plain argmax (first index on ties);
* top-k keeps every score >= the k-th largest (ties widen the set);
* top-p keeps score x iff the probability mass STRICTLY ABOVE x is
  < top_p * Z, computed over the top-k-filtered distribution;
* the draw inverts the CDF in vocab-index order: the sampled index is
  the first i with cumsum(p)[i] > u * total_mass, found by bisection
  over the index bits (a prefix mass is a masked sum).

Tunable: ``rows_per_step`` — logits rows per grid step (registry op
``sample_tokens``).  The logits are viewed as ``(tiles, rows_per_step,
V)`` so a block's last two dims are always whole, which the TPU's tiling
accepts for any row count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _order_keys(x):
    """f32 -> uint32 image with the same total order (sign-flip trick)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    sign = (bits >> jnp.uint32(31)).astype(bool)
    return jnp.where(sign, ~bits, bits | jnp.uint32(0x80000000))


def _bisect(n_bits, step, init):
    """MSB-first bisection as a ``fori_loop`` over ``n_bits`` bits:
    ``step(t, bit)`` returns the new lattice value from ``t`` and the
    candidate bit (a loop keeps the kernel's IR, and its compile, small)."""
    return jax.lax.fori_loop(
        0, n_bits, lambda i, t: step(t, n_bits - 1 - i), init)


def _kth_largest_key(keys, k):
    """Exact k-th largest uint32 key per row (keys: (R, V) -> (R, 1)).

    Greedy MSB-first bisection for the largest lattice value t with
    ``count(keys >= t) >= k``; since every key is a lattice point, t IS
    the k-th largest key.
    """
    def step(t, b):
        cand = t | (jnp.uint32(1) << b.astype(jnp.uint32))
        cnt = jnp.sum((keys >= cand).astype(jnp.int32), axis=1,
                      keepdims=True)
        return jnp.where(cnt >= k, cand, t)

    return _bisect(32, step, jnp.zeros((keys.shape[0], 1), jnp.uint32))


def _nucleus_keep(keys, p, budget):
    """Top-p keep mask: keep key x iff ``sum(p[keys > x]) < budget``.

    Bisection for the largest lattice t with mass-strictly-above >=
    budget; the kept set is then ``keys > t`` (or everything, when even
    the full strictly-above-minimum mass is under budget).
    """
    R = keys.shape[0]

    def strict_mass(t):
        return jnp.sum(jnp.where(keys > t, p, 0.0), axis=1, keepdims=True)

    def step(t, b):
        cand = t | (jnp.uint32(1) << b.astype(jnp.uint32))
        return jnp.where(strict_mass(cand) >= budget, cand, t)

    t = _bisect(32, step, jnp.zeros((R, 1), jnp.uint32))
    all_kept = strict_mass(jnp.zeros((R, 1), jnp.uint32)) < budget
    return all_kept | (keys > t)


def _first_argmax(x):
    """First index of each row's maximum, (R, V) -> (R, 1) int32."""
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    hit = x == jnp.max(x, axis=1, keepdims=True)
    return jnp.min(jnp.where(hit, idx, x.shape[1]), axis=1, keepdims=True)


def _inverse_cdf(p, target):
    """First index i with ``sum(p[:i+1]) > target`` per row (p >= 0,
    target < total mass).  MSB-first bisection for the count of leading
    entries whose prefix mass stays <= target; a prefix mass is a masked
    sum, monotone in the prefix length."""
    R, V = p.shape
    idx = jax.lax.broadcasted_iota(jnp.int32, (R, V), 1)

    def step(n, b):
        cand = n | (jnp.int32(1) << b)
        mass = jnp.sum(jnp.where(idx < cand, p, 0.0), axis=1, keepdims=True)
        return jnp.where((cand <= V - 1) & (mass <= target), cand, n)

    return _bisect((V - 1).bit_length(), step, jnp.zeros((R, 1), jnp.int32))


def _sampling_kernel(logits_ref, u_ref, o_ref, *, temperature, top_k,
                     top_p, vocab):
    l = logits_ref[0].astype(jnp.float32)              # (R, V)
    if temperature == 0.0:
        o_ref[0] = _first_argmax(l)
        return
    x = l / temperature
    keys = _order_keys(x)
    keep = jnp.ones_like(x, bool)
    if top_k is not None and 0 < top_k < vocab:
        keep &= keys >= _kth_largest_key(keys, top_k)
    m = jnp.max(x, axis=1, keepdims=True)              # argmax always kept
    p = jnp.where(keep, jnp.exp(x - m), 0.0)
    if top_p is not None and top_p < 1.0:
        budget = top_p * jnp.sum(p, axis=1, keepdims=True)
        p = jnp.where(_nucleus_keep(keys, p, budget), p, 0.0)
    total = jnp.sum(p, axis=1, keepdims=True)
    o_ref[0] = _inverse_cdf(p, u_ref[0] * total)      # u in [0,1) -> < total


def sample_tokens(logits, u, *, temperature=1.0, top_k=None, top_p=None,
                  rows_per_step=8, interpret=None):
    """Sample one token per row.  logits: (B, V); u: (B,) uniforms in
    [0, 1).  Returns (B,) int32.  ``temperature == 0`` is greedy argmax
    (u is ignored); ``top_k=None``/``top_p=None`` disable the cutoffs.
    """
    B, V = logits.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rb = max(1, min(int(rows_per_step), B))
    pad = (-B) % rb
    if pad:
        logits = jnp.pad(logits, [(0, pad), (0, 0)])
        u = jnp.pad(u, [(0, pad)])
    n_tiles = (B + pad) // rb

    kernel = functools.partial(
        _sampling_kernel, temperature=float(temperature),
        top_k=None if top_k is None else int(top_k),
        top_p=None if top_p is None else float(top_p), vocab=V)
    # every (rb, V) temporary (scores, keys, probabilities, masks) takes
    # whole (8, 128) tiles of VMEM; at vocab 151936 the default scoped
    # limit holds about three of them and the kernel keeps about eight
    tile = max(rb, 8) * (-(-V // 128) * 128) * 4
    vmem = min(max(8 * tile, 16 << 20), 100 << 20)
    row_spec = pl.BlockSpec((1, rb, 1), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((1, rb, V), lambda i: (i, 0, 0)), row_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, rb, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=vmem),
        interpret=interpret,
    )(logits.reshape(n_tiles, rb, V),
      u.astype(jnp.float32).reshape(n_tiles, rb, 1))
    return out.reshape(-1)[:B]
