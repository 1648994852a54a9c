"""Hierarchical on-mesh gradient synchronization (MXNet §3.3).

The paper's two-level KVStore aggregates gradients *within* a machine
first (level-1), then *across* machines (level-2), shrinking inter-machine
traffic by the devices-per-machine factor.  ``core/kvstore.py`` models
this analytically; this module is the on-mesh counterpart over a
``(pod, data, model)`` TPU mesh, where "machine" = pod and
"device-per-machine" = the ``data`` axis:

* ``mode="flat"`` — one all-reduce over the combined worker axes: every
  worker's full gradient crosses the pod boundary;
* ``mode="hierarchical"`` — reduce-scatter within each pod's ``data``
  axis (level-1: after it each worker holds a 1/|data| summed shard),
  an all-reduce of only that shard across ``pod`` (level-2), and an
  all-gather within ``data`` to restore the full replica.

* ``mode="bucketed"`` — the overlap-friendly schedule (DESIGN.md §7):
  leaves are packed into byte-capped buckets by a ``BucketPlan`` and each
  bucket is reduced with the hierarchical schedule (flat where the two
  coincide) as its own independent collective chain, so a scheduler can
  overlap bucket *k*'s sync with whatever produces bucket *k+1*.

* ``mode="eventual"`` — the paper's *eventual consistency* model
  (§2.3), on-mesh: the level-1 (intra-pod) reduction still runs every
  step, but each bucket's level-2 cross-pod exchange runs only on its
  scheduled step — a round-robin over ``max_staleness + 1`` phases —
  and off-schedule steps reuse the *stale* remote-pod contribution held
  in per-bucket versioned state (:class:`EventualSync`; DESIGN.md §15).
  Steady-state cross-pod bytes shrink by ``max_staleness + 1``×, and at
  ``max_staleness=0`` the schedule degenerates to the sequential
  (hierarchical) chain bit-for-bit.

All modes produce identical sums (eventual: identical at staleness 0,
bounded-staleness otherwise); the hierarchical HLO's cross-pod
all-reduce moves 1/|data| of the bytes — the §3.3 claim, checked from the
compiled HLO by ``tests/test_dist.py`` and benchmarked by
``benchmarks/bench_dist.py`` (which also checks that the per-bucket
cross-pod bytes sum back to the monolithic hierarchical total, and that
the per-phase eventual bytes match the analytic staleness model exactly).

Worked example (1-device fallback — runs anywhere)::

    >>> import jax, jax.numpy as jnp
    >>> from repro.dist import make_mesh
    >>> mesh = make_mesh((1,), ("data",))
    >>> grads = {"w": jnp.ones((4, 3))}      # 4 workers, one 3-vector each
    >>> out = gradient_sync(mesh, grads, mode="bucketed")
    >>> out["w"].tolist()                    # leading-dim sum, same tree
    [4.0, 4.0, 4.0]
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs

from . import compat
from .annotate import DATA_AXES
from .bucketing import DEFAULT_BUCKET_BYTES, BucketPlan

MODES = ("flat", "hierarchical", "bucketed", "eventual")


def worker_axes(mesh):
    """The mesh axes whose product is the gradient-worker count."""
    return tuple(a for a in DATA_AXES if a in mesh.axis_names)


def _flat_body(waxes):
    def sync(g):
        return jax.lax.psum(jnp.squeeze(g, 0), waxes)
    return sync


def _hier_body(n_data):
    def sync(g):
        g = jnp.squeeze(g, 0)
        shape, size = g.shape, g.size
        flat = g.reshape(-1)
        pad = (-size) % n_data
        if pad:
            flat = jnp.pad(flat, (0, pad))
        # level-1 reduce-scatter within the pod, spelled as all-to-all +
        # local sum (XLA backends without native reduce-scatter decompose
        # psum_scatter into a FULL-size all-reduce, which would defeat the
        # schedule); after this each data rank holds a 1/|data| summed shard
        with obs.named_scope("l1_reduce_scatter"):
            chunks = flat.reshape(n_data, -1)
            received = jax.lax.all_to_all(chunks, "data", split_axis=0,
                                          concat_axis=0, tiled=False)
            shard = received.sum(0)
        # level-2: only the 1/|data| shard crosses the pod boundary
        with obs.named_scope("l2_cross_pod"):
            shard = jax.lax.psum(shard, "pod")
        with obs.named_scope("l1_all_gather"):
            gathered = jax.lax.all_gather(shard, "data",
                                          axis=0)  # (n_data, c)
        full = gathered.reshape(-1)
        if pad:
            full = full[:size]
        return full.reshape(shape)
    return sync


def gradient_sync(mesh, grads, mode: str = "flat", *,
                  bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                  plan: BucketPlan | None = None):
    """Sum a pytree of per-worker gradients over their leading worker dim.

    Every leaf of ``grads`` has shape ``(W, ...)`` with ``W`` the product
    of the mesh's worker axes (``pod`` × ``data``); the result is the
    leading-dim sum, replicated over the mesh.  ``mode="hierarchical"``
    falls back to flat when the mesh has no ``pod`` axis or no multi-way
    ``data`` axis (the two schedules coincide there).

    ``mode="bucketed"`` packs the leaves into ``bucket_bytes``-capped
    buckets (``plan`` overrides the packing; its byte accounting is
    per-worker, i.e. excludes the leading ``W`` dim) and reduces each
    bucket with the hierarchical schedule as an independent collective
    chain.  Numerically identical to the other modes.

    ``mode="eventual"`` is the *stateless* entry to the bounded-staleness
    schedule: a single isolated sync always starts warm (every bucket's
    cross-pod exchange is fresh), so it coincides with ``bucketed``
    bit-for-bit.  Steady-state staleness lives across steps — hold an
    :class:`EventualSync` and thread its state for that.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if mode in ("bucketed", "eventual"):
        leaves, treedef = jax.tree.flatten(grads)
        plan = plan or BucketPlan.build(leaves, cap_bytes=bucket_bytes,
                                        lead_dims=1)
        buffers = plan.pack(leaves, lead_dims=1)
        synced = gradient_sync(mesh, buffers, mode="hierarchical")
        return treedef.unflatten(plan.unpack(synced, leaves, lead_dims=1))
    waxes = worker_axes(mesh)
    sizes = dict(mesh.shape)
    n_workers = 1
    for a in waxes:
        n_workers *= sizes[a]
    if not waxes or n_workers == 1 or mesh.size == 1:
        return jax.tree.map(lambda g: g.sum(0), grads)
    for g in jax.tree.leaves(grads):
        if g.shape[0] != n_workers:
            raise ValueError(
                f"gradient leaf has leading dim {g.shape[0]}, expected the "
                f"worker count {n_workers} (= product of mesh axes {waxes})")
    if (mode == "hierarchical" and "pod" in mesh.axis_names
            and sizes.get("data", 1) > 1):
        body = _hier_body(sizes["data"])
    else:
        # single-pod or no intra-pod data axis: the two schedules coincide
        body = _flat_body(waxes)
    def tree_sync(t):
        # one named scope per leaf: under mode="bucketed" the leaves ARE
        # the packed buckets, so a device profile shows each bucket's
        # collective chain (grad_sync_b0, grad_sync_b1, ...) as the
        # independent region a scheduler may overlap with compute
        leaves, treedef = jax.tree.flatten(t)
        out = []
        for k, g in enumerate(leaves):
            with obs.named_scope(f"grad_sync_b{k}"):
                out.append(body(g))
        return treedef.unflatten(out)

    # all axes manual (inputs have no "model" dim; full-manual also works
    # eagerly, where partial-auto does not on older jax)
    sync = compat.shard_map(tree_sync, mesh,
                            in_specs=(P(waxes),), out_specs=P())
    return sync(grads)


# ---------------------------------------------------------------------------
# eventual consistency: bounded-staleness cross-pod sync (DESIGN.md §15)

def eventual_sync_buckets(n_buckets: int, max_staleness: int,
                          phase: int, warm: bool = False) -> tuple[int, ...]:
    """Bucket indices whose cross-pod exchange runs at ``phase``.

    The schedule is a static round-robin over ``max_staleness + 1``
    phases: bucket *b* syncs when ``b % period == phase``.  A ``warm``
    step (the first step of a run) syncs every bucket, so no bucket ever
    serves an uninitialized remote contribution.

    >>> eventual_sync_buckets(4, 1, 0)
    (0, 2)
    >>> eventual_sync_buckets(4, 3, 2)
    (2,)
    >>> eventual_sync_buckets(4, 3, 1, warm=True)
    (0, 1, 2, 3)
    """
    period = max_staleness + 1
    if warm:
        return tuple(range(n_buckets))
    return tuple(b for b in range(n_buckets) if b % period == phase % period)


def _bucket_shard_elems(bucket, n_data: int) -> int:
    """Per-device level-2 shard length of one bucket: the per-worker
    payload padded up to a multiple of the intra-pod ``data`` axis."""
    return -(-bucket.n_elems // max(n_data, 1))


def eventual_crosspod_bytes(plan: BucketPlan, n_data: int, *,
                            max_staleness: int, phase: int | None = None,
                            warm: bool = False) -> int:
    """Analytic cross-pod all-reduce *result* bytes of one eventual-sync
    step (the quantity ``benchmarks/bench_dist.py`` reads off the
    compiled HLO): each syncing bucket contributes its 1/``n_data``
    level-2 shard.  ``phase=None`` with ``warm=True`` is the first-step
    full sync (== the monolithic hierarchical total for the same plan).
    """
    idx = eventual_sync_buckets(plan.n_buckets, max_staleness,
                                0 if phase is None else phase, warm=warm)
    return sum(_bucket_shard_elems(plan.buckets[b], n_data)
               * jnp.dtype(plan.buckets[b].dtype).itemsize for b in idx)


def eventual_state_bytes(plan: BucketPlan, n_data: int,
                         n_workers: int) -> dict:
    """Device bytes of the :class:`EventualSync` remote-shard state: one
    1/``n_data`` shard per bucket per worker (``core/memplan`` re-exports
    this for footprint reports; exact vs the real state arrays)."""
    per_worker = sum(_bucket_shard_elems(b, n_data)
                     * jnp.dtype(b.dtype).itemsize for b in plan.buckets)
    return {"per_worker": per_worker, "total": per_worker * n_workers,
            "n_buckets": plan.n_buckets}


class EventualSync:
    """Bounded-staleness cross-pod gradient sync (MXNet §2.3 eventual
    consistency, on-mesh; DESIGN.md §15).

    Holds a :class:`BucketPlan` over the gradient leaves plus *versioned
    per-bucket state*: for every bucket, each worker keeps the stale
    remote-pod level-2 shard it received at that bucket's last scheduled
    exchange, and a host-side version (the step of that exchange).  Per
    step:

    * level-1 always runs — reduce-scatter within the pod's ``data``
      axis, so each worker holds a fresh 1/|data| shard of its *pod's*
      sum (the cheap intra-machine traffic);
    * level-2 runs only for the buckets scheduled at this step's phase
      (``step % (max_staleness + 1)``): those push their shard across
      ``pod``, receive the fresh global shard, and store
      ``global − local`` as the new remote state (the versioned
      push/pull).  Off-schedule buckets *pull* their stale remote shard
      from state instead — zero cross-pod bytes;
    * an all-gather within ``data`` restores the full replica either way.

    Scheduled buckets return the fresh global shard itself (not
    ``local + (global − local)``), so ``max_staleness=0`` — every bucket
    scheduled every step — reproduces ``gradient_sync(mode="bucketed")``
    bit-for-bit.  Observed staleness is ``step − version`` and never
    exceeds ``max_staleness`` (warm first step + round-robin period;
    property-tested in ``tests/test_eventual.py``).

    On a mesh without a multi-way ``pod`` axis there is no cross-pod
    boundary to be stale over: the sync degenerates to the every-step
    flat/hierarchical sum with empty state (``degenerate`` is True).

    Usage (``apply`` is traceable — call it inside an enclosing jit with
    a static ``phase``; ``phase_for``/``record_step`` do the host-side
    bookkeeping)::

        ev = EventualSync(mesh, grads_template, max_staleness=2)
        state = ev.init_state()
        for step in range(n_steps):
            phase, warm = ev.phase_for(step)
            synced, state = jitted[phase, warm](grads, state)
            ev.record_step(step)
    """

    def __init__(self, mesh, template, *, max_staleness: int = 0,
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 plan: BucketPlan | None = None):
        if max_staleness < 0:
            raise ValueError(f"max_staleness must be >= 0, "
                             f"got {max_staleness}")
        self.mesh = mesh
        self.max_staleness = max_staleness
        self.period = max_staleness + 1
        leaves, self.treedef = jax.tree.flatten(template)
        self.waxes = worker_axes(mesh)
        sizes = dict(mesh.shape)
        self.n_workers = 1
        for a in self.waxes:
            self.n_workers *= sizes[a]
        self.n_data = sizes.get("data", 1) if "data" in mesh.axis_names else 1
        self.n_pod = sizes.get("pod", 1) if "pod" in mesh.axis_names else 1
        # no multi-way pod axis -> no cross-pod boundary -> nothing to be
        # stale over; 1-worker/1-device meshes also have nothing to sync
        self.degenerate = (self.n_pod <= 1 or not self.waxes
                          or self.n_workers == 1 or mesh.size == 1)
        for g in leaves:
            if not self.degenerate and g.shape[0] != self.n_workers:
                raise ValueError(
                    f"gradient leaf has leading dim {g.shape[0]}, expected "
                    f"the worker count {self.n_workers}")
        self.plan = plan or BucketPlan.build(leaves, cap_bytes=bucket_bytes,
                                            lead_dims=1)
        self.n_buckets = self.plan.n_buckets
        # host-side versioning: step of each bucket's last level-2
        # exchange; None until the warm first step runs
        self.versions: list[int | None] = [None] * self.n_buckets
        self.max_observed_staleness = 0
        self._started = False

    # -- schedule ----------------------------------------------------------
    def phase_for(self, step: int) -> tuple[int, bool]:
        """``(phase, warm)`` for a step — both Python ints/bools, meant to
        select a jit-specialized variant (the schedule is static)."""
        return step % self.period, not self._started

    def sync_buckets(self, phase: int, warm: bool = False) -> tuple[int, ...]:
        if self.degenerate:
            return tuple(range(self.n_buckets))
        return eventual_sync_buckets(self.n_buckets, self.max_staleness,
                                     phase, warm=warm)

    def record_step(self, step: int) -> int:
        """Host bookkeeping after running a step: advance per-bucket
        versions, publish per-mode obs counters, and return the maximum
        staleness observed at this step."""
        phase, warm = self.phase_for(step)
        synced = set(self.sync_buckets(phase, warm=warm))
        stale = 0
        for b in range(self.n_buckets):
            if b in synced or self.versions[b] is None:
                self.versions[b] = step
            else:
                stale = max(stale, step - self.versions[b])
        self.max_observed_staleness = max(self.max_observed_staleness, stale)
        self._started = True
        m = obs.get_metrics()
        m.counter("dist.sync.eventual.steps").inc()
        m.counter("dist.sync.eventual.crosspod_bytes").inc(
            self.crosspod_allreduce_bytes(phase, warm=warm))
        m.gauge("dist.sync.eventual.max_staleness_observed").set(
            self.max_observed_staleness)
        return stale

    # -- analytic byte/state models ---------------------------------------
    def crosspod_allreduce_bytes(self, phase: int, warm: bool = False) -> int:
        """Cross-pod all-reduce result bytes this phase's compiled step
        moves (0 on degenerate meshes) — the HLO-cross-validated model."""
        if self.degenerate:
            return 0
        return eventual_crosspod_bytes(self.plan, self.n_data,
                                       max_staleness=self.max_staleness,
                                       phase=phase, warm=warm)

    def state_bytes(self) -> dict:
        if self.degenerate:
            return {"per_worker": 0, "total": 0, "n_buckets": self.n_buckets}
        return eventual_state_bytes(self.plan, self.n_data, self.n_workers)

    # -- state -------------------------------------------------------------
    def init_state(self) -> dict:
        """Zero remote shards, laid out ``(W, shard)`` with the worker dim
        sharded over the worker axes (``make_array_from_callback`` so the
        same code works single- and multi-process).  The callback returns
        host arrays: a ``jnp`` array made under the ambient mesh would
        span every process's devices, which ``device_put`` refuses."""
        if self.degenerate:
            return {}
        from jax.sharding import NamedSharding
        sharding = NamedSharding(self.mesh, P(self.waxes))
        out = {}
        for k, bucket in enumerate(self.plan.buckets):
            shape = (self.n_workers, _bucket_shard_elems(bucket, self.n_data))
            dt = jnp.dtype(bucket.dtype)

            def zeros_shard(idx, shape=shape, dt=dt):
                local = tuple(len(range(*s.indices(n)))
                              for s, n in zip(idx, shape))
                return np.zeros(local, dt)

            out[f"b{k}"] = jax.make_array_from_callback(shape, sharding,
                                                        zeros_shard)
        return out

    # -- the sync itself ---------------------------------------------------
    def apply(self, grads, state, *, phase: int, warm: bool = False):
        """``(synced_grads, new_state)`` — traceable; ``phase``/``warm``
        are static (each pair lowers to a distinct collective schedule,
        which is what makes the per-phase HLO byte model exact)."""
        if self.degenerate:
            return gradient_sync(self.mesh, grads, mode="bucketed",
                                 plan=self.plan), state
        leaves = jax.tree.flatten(grads)[0]
        buffers = self.plan.pack(leaves, lead_dims=1)
        st = [state[f"b{k}"] for k in range(self.n_buckets)]
        syncing = set(self.sync_buckets(phase, warm=warm))
        n_data, has_data = self.n_data, "data" in self.mesh.axis_names

        def body(bufs, rems):
            out_b, out_r = [], []
            for k, (buf, rem) in enumerate(zip(bufs, rems)):
                tag = "push" if k in syncing else "stale"
                with obs.named_scope(f"ev_sync_b{k}_{tag}"):
                    g = jnp.squeeze(buf, 0)
                    remote = jnp.squeeze(rem, 0)
                    size = g.size
                    pad = (-size) % n_data
                    flat = jnp.pad(g, (0, pad)) if pad else g
                    if has_data and n_data > 1:
                        # level-1: reduce-scatter within the pod (all-to-all
                        # + local sum, as in the hierarchical schedule)
                        chunks = flat.reshape(n_data, -1)
                        received = jax.lax.all_to_all(
                            chunks, "data", split_axis=0, concat_axis=0,
                            tiled=False)
                        shard = received.sum(0)
                    else:
                        shard = flat
                    if k in syncing:
                        # level-2 push/pull: fresh global shard crosses
                        # the pod boundary; remote = global - local is the
                        # versioned pull served on off-schedule steps
                        out_shard = jax.lax.psum(shard, "pod")
                        new_remote = out_shard - shard
                    else:
                        out_shard = shard + remote
                        new_remote = remote
                    if has_data and n_data > 1:
                        gathered = jax.lax.all_gather(out_shard, "data",
                                                      axis=0)
                        full = gathered.reshape(-1)
                    else:
                        full = out_shard
                    if pad:
                        full = full[:size]
                    out_b.append(full)
                    out_r.append(new_remote[None])
            return tuple(out_b), tuple(out_r)

        n = self.n_buckets
        fn = compat.shard_map(
            body, self.mesh,
            in_specs=((P(self.waxes),) * n, (P(self.waxes),) * n),
            out_specs=((P(),) * n, (P(self.waxes),) * n))
        out_bufs, out_rems = fn(tuple(buffers), tuple(st))
        synced_leaves = self.plan.unpack(list(out_bufs), leaves, lead_dims=1)
        synced = self.treedef.unflatten(synced_leaves)
        return synced, {f"b{k}": out_rems[k] for k in range(n)}
