"""Grouped SwiGLU over the experts a chip holds, as a Pallas TPU kernel.

The rows of an expert layer arrive sorted by expert, each expert's rows
padded up to a whole tile of ``block_rows`` (``models/moe.py`` builds
that layout), so every row tile belongs to one expert.  The kernel
computes, for each row, its own expert's ``silu(x Wg) * (x Wu) Wd`` and
nothing for any other expert: a grouped matmul, with the group sizes
given as each tile's expert (``tile_group``) and the number of tiles that
hold rows (``n_tiles``).

* grid = (row tiles, F / block_ff); the F axis is a reduction (the down
  projection accumulates over it in a VMEM f32 scratch), so both axes
  are "arbitrary";
* scalar prefetch: ``tile_group`` and ``n_tiles`` ride ahead of the grid,
  and the weight BlockSpecs' index maps read the tile's expert out of
  ``tile_group``: the DMA engine fetches that expert's (D, block_ff)
  slices of Wg and Wu and (block_ff, D) slice of Wd;
* the static row count is the layer's worst case (every token's choices
  among the held experts, plus each group's padding); tiles at or past
  ``n_tiles`` keep the block indices of the last live step, so no weight
  or row block is fetched again for them, and they compute nothing.
  Their output rows are left unwritten: the caller reads only rows that
  hold a routed token;
* accumulation in f32, output in the rows' dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT = 64 * 1024 * 1024


def _gmm_kernel(tg_ref, nt_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
                acc_ref, *, n_ff):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i < nt_ref[0])
    def _tile():
        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        acc_ref[...] += jnp.dot(h, wd_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(j == n_ff - 1)
        def _done():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def expert_gmm(x, wg, wu, wd, tile_group, n_tiles, *, block_rows,
               block_ff=256, interpret=None):
    """x: (M, D) rows sorted by expert, M a multiple of ``block_rows``;
    wg, wu: (E, D, F) and wd: (E, F, D), the held experts' weights;
    tile_group: (M // block_rows,) int32, the expert of each row tile;
    n_tiles: int32 scalar, the tiles that hold rows (those after them are
    skipped).  ``block_ff`` (a schedule knob) is the F slice a grid step
    takes; it falls back to F where it does not divide it.

    Returns (M, D) in x's dtype; rows of skipped tiles are unwritten."""
    M, D = x.shape
    E, _, F = wg.shape
    tm = int(block_rows)
    assert M % tm == 0, (M, tm)
    tf = int(block_ff) if F % int(block_ff) == 0 else F
    n_ff = F // tf
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def live(i, j, n):
        # a skipped tile repeats the last live step's block indices
        last = jnp.maximum(n[0], 1) - 1
        keep = i <= last
        return jnp.where(keep, i, last), jnp.where(keep, j, n_ff - 1)

    def rows_map(i, j, tg, n):
        return live(i, j, n)[0], 0

    def up_map(i, j, tg, n):
        ii, jj = live(i, j, n)
        return tg[ii], 0, jj

    def down_map(i, j, tg, n):
        ii, jj = live(i, j, n)
        return tg[ii], jj, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(M // tm, n_ff),
        in_specs=[pl.BlockSpec((tm, D), rows_map),
                  pl.BlockSpec((None, D, tf), up_map),
                  pl.BlockSpec((None, D, tf), up_map),
                  pl.BlockSpec((None, tf, D), down_map)],
        out_specs=pl.BlockSpec((tm, D), rows_map),
        scratch_shapes=[pltpu.VMEM((tm, D), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, n_ff=n_ff),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="expert_gmm",
        interpret=interpret,
    )(tile_group.astype(jnp.int32),
      jnp.reshape(n_tiles, (1,)).astype(jnp.int32), x, wg, wu, wd)
