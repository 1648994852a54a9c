"""PartitionSpec rule tables: params, batches, KV/SSM caches.

One table covers every config in ``repro/configs`` — dense GQA, MoE, SSM,
hybrid, VLM-prefix and enc-dec — because rules are written per *role*
(leaf name in the param pytree) and resolved against the concrete shapes
through the same divisibility-dropping machinery as ``annotate.ann``:
an axis that does not divide a dim is dropped, never an error.

Layout policy (megatron-style tensor parallel + zero-style FSDP):

* attention/MLP weights — contraction-adjacent "wide" dim over ``model``
  (heads for wq/wo, KV heads for wk/wv, d_ff for wg/wu/wd), one other
  large dim over ``data`` (FSDP, gathered on use);
* MoE expert weights — experts over ``model`` (expert parallelism: the
  group→expert reshard is the all-to-all), second dim over ``data``;
* embeddings — vocab over ``model`` (vocab-parallel embedding/logits),
  d_model over ``data``;
* SSM — the fused in/out projections over ``model``, tiny per-head
  params replicated;
* norms / biases / scalars — replicated;
* batches — leading (batch) dim over the data axes;
* caches — batch over data, KV-heads / SSM-heads over ``model``.

Params stacked along a leading ``n_super`` (or encoder-depth) axis get a
``None`` prepended: the scan axis is never sharded.

Worked example — a stacked attention projection on a 1-device dev-box
mesh (no "data" axis, so FSDP entries resolve to ``None``; the scan axis
gets the prepended ``None``)::

    >>> import jax
    >>> from repro.dist import make_mesh
    >>> mesh = make_mesh((1,), ("model",))
    >>> params = {"blocks": {"wq": jax.ShapeDtypeStruct((4, 8, 2, 16),
    ...                                                 "float32")}}
    >>> specs = param_pspecs(cfg=None, params=params, mesh=mesh)
    >>> specs["blocks"]["wq"] == P(None, None, "model", None)
    True
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import DictKey, tree_map_with_path

from .annotate import BATCH, _resolve

# role -> spec entries for the UNSTACKED shape (see module docstring)
_PARAM_RULES = {
    "embed": ("model", "data"),            # (V, D)
    "lm_head": ("data", "model"),          # (D, V)
    "wq": ("data", "model", None),         # (D, H, hd)
    "wk": ("data", "model", None),         # (D, K, hd)
    "wv": ("data", "model", None),         # (D, K, hd)
    "wo": ("model", None, "data"),         # (H, hd, D)
    "bq": ("model", None),                 # (H, hd)
    "bk": ("model", None),
    "bv": ("model", None),
    "wg": ("data", "model"),               # (D, F)
    "wu": ("data", "model"),
    "wd": ("model", "data"),               # (F, D)
    "shared_wg": ("data", "model"),
    "shared_wu": ("data", "model"),
    "shared_wd": ("model", "data"),
    "router": ("data", None),              # (D, E) — router stays small
    "in_proj": ("data", "model"),          # (D, 2di+2N+H)
    "out_proj": ("model", "data"),         # (di, D)
    "conv_w": (None, "model"),             # (W, ch)
    "conv_b": ("model",),                  # (ch,)
    "dt_bias": (None,),                    # (H,) — tiny, replicate
    "A_log": (None,),
    "D": (None,),
    "frontend_proj": (None, "model"),      # (frontend_dim, D)
}

# MoE expert tensors share names with the dense MLP but carry a leading
# expert dim: (E, D, F) / (E, F, D) — experts over "model"
_MOE_EXPERT_RULE = ("model", "data", None)


def _generic(ndim):
    """Fallback for unknown roles: first dim FSDP, last dim model."""
    if ndim <= 1:
        return (None,) * ndim
    return ("data",) + (None,) * (ndim - 2) + ("model",)


def _path_keys(path):
    return [k.key for k in path if isinstance(k, DictKey)]


def _rule_spec(keys, shape, names, sizes, stage_axis=None):
    """The role rule table applied to ONE leaf identified by its dict
    key path — shared by ``param_pspecs`` (live pytrees) and
    ``spec_for_path`` (checkpoint-manifest paths)."""
    name = keys[-1] if keys else ""
    in_blocks = any(k == "blocks" for k in keys[:-1])
    stacked = in_blocks or any(k == "encoder" for k in keys[:-1])
    base_ndim = len(shape) - (1 if stacked else 0)
    if name in ("wg", "wu", "wd") and "moe" in keys:
        entries = _MOE_EXPERT_RULE
    else:
        entries = _PARAM_RULES.get(name)
    if entries is None or len(entries) != base_ndim:
        entries = _generic(base_ndim)
    if stacked:
        lead = stage_axis if (stage_axis and in_blocks) else None
        entries = (lead,) + tuple(entries)
    return _resolve(entries, shape, names, sizes)


def spec_for_path(keys, shape, mesh, stage_axis: str | None = None):
    """Single-leaf spec lookup by pytree key path (DESIGN.md §12).

    The same rule table ``param_pspecs`` applies tree-wide, exposed for
    the checkpoint layer's elastic restore, where a leaf arrives as a
    manifest key path plus a global shape rather than a live pytree —
    ``spec_for_path(["params", "blocks", "wq"], (4, 8, 2, 16), mesh)``
    resolves against the *target* mesh, so the same checkpoint restores
    onto any layout.  Works for optimizer-state mirrors too: the role
    name is the last key, wherever the subtree is nested.
    """
    names, sizes = tuple(mesh.axis_names), dict(mesh.shape)
    return _rule_spec(list(keys), tuple(shape), names, sizes, stage_axis)


def param_pspecs(cfg, params, mesh, stage_axis: str | None = None):
    """PartitionSpec pytree matching ``params`` (arrays or
    ShapeDtypeStructs), every sharded dim guaranteed to divide.

    ``stage_axis``: pipeline parallelism (DESIGN.md §10) — the leading
    scan dim of ``blocks`` leaves is sharded over this mesh axis instead
    of staying unsharded, placing layer-contiguous super-block groups on
    each pipeline stage (``dist.pipeline.stage_pspecs`` is the public
    wrapper).  As everywhere, a non-dividing axis is dropped.
    """
    names, sizes = tuple(mesh.axis_names), dict(mesh.shape)

    def rule(path, leaf):
        return _rule_spec(_path_keys(path), tuple(leaf.shape), names,
                          sizes, stage_axis)

    return tree_map_with_path(rule, params)


def batch_pspecs(cfg, batch, mesh, kind: str = "train"):
    """Batch inputs: leading dim over the data axes, rest replicated.

    The same rule serves train/prefill/decode; ``kind="seq"`` is the
    sequence-sharded long-context layout (DESIGN.md §8): dim 1 — the
    sequence — additionally over ``model``, feeding the ring-attention
    path with already-S-sharded tokens so the embedding lookup and the
    residual stream never materialize the full sequence per device.  As
    everywhere, a non-dividing axis is dropped, never an error.
    """
    names, sizes = tuple(mesh.axis_names), dict(mesh.shape)
    seq = kind == "seq"

    def rule(leaf):
        shape = tuple(leaf.shape)
        if not shape:
            return P()
        entries = [BATCH] + [None] * (len(shape) - 1)
        if seq and len(shape) >= 2:
            entries[1] = "model"
        return _resolve(tuple(entries), shape, names, sizes)

    return jax.tree.map(rule, batch)


def cache_pspecs(cfg, cache, mesh):
    """KV/SSM cache pytrees: batch over data, head dims over ``model``.

    Cache leaves carry a leading ``n_super`` scan axis:
    ``k/v (n_super, B, S, K, hd)``, ``conv (n_super, B, W-1, ch)``,
    ``ssm (n_super, B, H, P, N)``; ``pos`` is a replicated scalar.
    """
    names, sizes = tuple(mesh.axis_names), dict(mesh.shape)

    def rule(path, leaf):
        keys = _path_keys(path)
        name = keys[-1] if keys else ""
        shape = tuple(leaf.shape)
        if not shape or name == "pos":
            return P()
        if name == "conv":
            entries = (None, BATCH, None, "model")
        elif name == "ssm":
            entries = (None, BATCH, "model", None, None)
        elif len(shape) == 5:  # k/v and encoder cross-KV tensors
            entries = (None, BATCH, None, "model", None)
        else:
            entries = (None, BATCH) + (None,) * (len(shape) - 2)
        return _resolve(entries, shape, names, sizes)

    return tree_map_with_path(rule, cache)


def make_shardings(mesh, pspecs):
    """PartitionSpec pytree -> NamedSharding pytree on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda s: isinstance(s, P))
