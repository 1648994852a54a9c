"""repro.dist — sharding annotations, partition rules, on-mesh collectives.

The distribution layer of the reproduction (DESIGN.md §5, §7):

* ``annotate`` — per-tensor sharding constraints over a named mesh with a
  graceful no-mesh/1-device fallback (model code is annotation-transparent
  on CPU);
* ``partition`` — PartitionSpec rule tables for params / batches / caches
  covering every config in ``repro/configs``;
* ``collectives`` — ``gradient_sync``: flat vs the paper's §3.3 two-level
  (hierarchical) gradient all-reduce over a ``(pod, data, model)`` mesh,
  plus the bucketed overlap-friendly schedule and ``EventualSync`` — the
  §3.3 eventual-consistency KVStore as bounded-staleness cross-pod sync
  (round-robin bucket schedule, analytic byte/state models, DESIGN.md
  §15);
* ``bucketing`` — ``BucketPlan`` (first-fit byte-capped gradient packing)
  and ``overlap_taps`` (the custom_vjp trick that emits each bucket's
  sync inside the backward computation — the §4 lazy-push analogue);
* ``ring`` — sequence-sharded exact attention as a rotating k/v
  collective-permute schedule with a reverse-ring ``custom_vjp``
  (DESIGN.md §8), plus its analytic permute-byte model;
* ``pipeline`` — the "stage" mesh axis: layer-contiguous super-block
  groups with a 1F1B micro-batch schedule, collective-permute activation
  hand-offs and a reverse-schedule ``custom_vjp`` (DESIGN.md §10), plus
  its analytic bubble/permute-byte models;
* ``compat`` — ``make_mesh`` (every axis ``Auto``, so annotations are
  partitioner hints), ``current_mesh`` and a partial-manual
  ``shard_map``.

Worked example — the full surface on a dev box (1 device, so every
annotation is the identity and collectives degrade to local sums)::

    >>> import jax, jax.numpy as jnp
    >>> x = ann(jnp.ones((8, 16)), BATCH, "model")   # no mesh: identity
    >>> x.shape
    (8, 16)
    >>> mesh = make_mesh((1,), ("data",))
    >>> grads = {"w": jnp.ones((4, 6)), "b": jnp.ones((4, 2))}
    >>> out = gradient_sync(mesh, grads, mode="bucketed")
    >>> {k: v.shape for k, v in sorted(out.items())}
    {'b': (2,), 'w': (6,)}
    >>> plan = BucketPlan.build(jax.tree.leaves(grads), cap_bytes=1 << 20,
    ...                         lead_dims=1)
    >>> plan.n_buckets, plan.assignment()
    (1, (0, 0))
"""
from .annotate import BATCH, DATA_AXES, ann, ann_first_fit, _mesh_axes
from .compat import make_mesh
from .bucketing import (DEFAULT_BUCKET_BYTES, Bucket, BucketPlan,
                        leaf_nbytes, overlap_taps)
from .collectives import (EventualSync, eventual_crosspod_bytes,
                          eventual_state_bytes, eventual_sync_buckets,
                          gradient_sync, worker_axes)
from .partition import (batch_pspecs, cache_pspecs, make_shardings,
                        param_pspecs)
from .pipeline import (PipelineSpec, pipeline_bubble_fraction,
                       pipeline_permute_bytes, pipeline_stack, stage_pspecs,
                       validate_pipeline)
from .ring import RingSpec, contributing_steps, ring_attention, \
    ring_permute_bytes

__all__ = [
    "BATCH", "DATA_AXES", "ann", "ann_first_fit", "_mesh_axes", "make_mesh",
    "gradient_sync", "worker_axes", "EventualSync",
    "eventual_sync_buckets", "eventual_crosspod_bytes",
    "eventual_state_bytes",
    "Bucket", "BucketPlan", "DEFAULT_BUCKET_BYTES", "leaf_nbytes",
    "overlap_taps",
    "param_pspecs", "batch_pspecs", "cache_pspecs", "make_shardings",
    "PipelineSpec", "pipeline_bubble_fraction", "pipeline_permute_bytes",
    "pipeline_stack", "stage_pspecs", "validate_pipeline",
    "RingSpec", "contributing_steps", "ring_attention",
    "ring_permute_bytes",
]
