"""Training module (MXNet §2.4): trains a model given a symbolic module
and data iterators, "optionally distributedly if an additional KVStore is
provided" — the paper's loop verbatim:

    while(1) { kv.pull(net.w); net.forward_backward(); kv.push(net.g); }

Two backends:
  * ``jit``   — single-process pjit path (CPU smoke / TPU production);
    gradient sync is implicit (GSPMD) or via dist.collectives.
  * ``kvstore`` — the engine-scheduled path: gradients flow through a
    KVStore (local or the multi-worker simulation with sequential/eventual
    consistency), exercising C3/C4/C7 end-to-end.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import ArchConfig, get_model
from repro.obs import MetricsLogger
from repro.optim import sgd_momentum, warmup_cosine
from repro.optim.optimizers import Optimizer

from .checkpoint import AsyncCheckpointer


@dataclass
class TrainConfig:
    lr: float = 3e-4
    mu: float = 0.9
    weight_decay: float = 1e-4
    warmup_steps: int = 20
    total_steps: int = 200
    log_every: int = 10
    checkpoint_every: int = 0
    checkpoint_dir: str = "checkpoints"
    # sharded checkpointing (DESIGN.md §12): async finalization keeps
    # only the device->host shard snapshot on the step critical path;
    # serialization + two-phase commit run on a background thread.
    # checkpoint_keep prunes committed step_* dirs beyond the newest N.
    checkpoint_async: bool = True
    checkpoint_keep: int = 3
    grad_clip: float = 1.0
    # bucketed gradient sync emitted inside backward (DESIGN.md §7):
    # the §4 lazy-push analogue on the jit path. Numerically identical to
    # overlap=False; only the collective schedule changes.
    overlap: bool = False
    bucket_mb: float = 4.0
    # pipeline parallelism over the super-block stack (DESIGN.md §10):
    # number of "stage" mesh-axis groups (1 = off) and micro-batches
    # streamed through the 1F1B schedule.  Selects PerfFlags.pp_stages /
    # .microbatches; validated against the arch in Trainer.__init__.
    pp_stages: int = 1
    microbatches: int = 1
    # cross-worker gradient sync (DESIGN.md §15): "auto" leaves the
    # reduction to GSPMD (implicit, the default); "sequential" computes
    # per-worker grads explicitly and reduces them with the two-level
    # bucketed schedule every step; "eventual" additionally bounds each
    # bucket's cross-pod exchange to every max_staleness+1 steps
    # (EventualSync — the paper's §2.3 eventual-consistency KVStore).
    # Explicit modes degrade to "auto" when the ambient mesh has <= 1
    # gradient worker.
    sync_mode: str = "auto"
    max_staleness: int = 0


class Trainer:
    def __init__(self, cfg: ArchConfig, tcfg: TrainConfig,
                 optimizer: Optimizer | None = None,
                 logger: MetricsLogger | None = None):
        self.cfg = cfg
        self.tcfg = tcfg
        # stdout sink by default — a bare run logs exactly like before;
        # launch --metrics swaps in/adds the JSONL sink (DESIGN.md §11)
        self.logger = logger if logger is not None else MetricsLogger()
        if tcfg.pp_stages > 1 or tcfg.microbatches > 1:
            from repro.dist.pipeline import validate_pipeline
            from repro.perf_flags import FLAGS, set_flags
            validate_pipeline(n_stages=tcfg.pp_stages,
                              microbatches=tcfg.microbatches,
                              n_super=cfg.n_super,
                              seq_shard=FLAGS.seq_shard)
            set_flags(pp_stages=tcfg.pp_stages,
                      microbatches=tcfg.microbatches)
        if tcfg.sync_mode not in ("auto", "sequential", "eventual"):
            raise ValueError(f"sync_mode must be auto|sequential|eventual, "
                             f"got {tcfg.sync_mode!r}")
        if tcfg.sync_mode != "auto" and (tcfg.pp_stages > 1 or tcfg.overlap):
            raise ValueError("explicit sync_mode is incompatible with "
                             "pipeline parallelism and overlap taps")
        # eventual-sync runtime state (built lazily in fit, when the
        # params template and ambient mesh are known)
        self._ev = None
        self._ev_steps: dict = {}
        self.model = get_model(cfg)
        self.optimizer = optimizer or sgd_momentum(
            lr=tcfg.lr, mu=tcfg.mu, weight_decay=tcfg.weight_decay)
        self.schedule = warmup_cosine(tcfg.warmup_steps, tcfg.total_steps)
        self.history: list[dict] = []
        # sharded checkpoint manager (DESIGN.md §12), created only when
        # checkpointing is on — fit() enqueues, exit waits for the commit
        self.checkpointer = (AsyncCheckpointer(
            tcfg.checkpoint_dir, keep=tcfg.checkpoint_keep,
            async_save=tcfg.checkpoint_async)
            if tcfg.checkpoint_every else None)

    # ------------------------------------------------------------------
    def init_state(self, seed: int = 0):
        params = self.model.init(jax.random.PRNGKey(seed))
        opt = self.optimizer.init(params)
        return params, opt

    def _make_step(self):
        model, optimizer, schedule = self.model, self.optimizer, self.schedule
        clip = self.tcfg.grad_clip
        overlap = self.tcfg.overlap
        bucket_bytes = max(int(self.tcfg.bucket_mb * 2**20), 1)

        pp = self.tcfg.pp_stages > 1

        def loss_fn(params, batch):
            if overlap:
                # route params through per-bucket custom_vjp taps so each
                # bucket's gradient reduction is emitted inside backward.
                # Under pipeline parallelism the block stack is excluded:
                # its grads are stage-sharded and already reduced over the
                # data axes inside the pipeline backward — a replicated
                # bucket pin would all-gather them over "stage"
                # (DESIGN.md §10); taps cover the replicated params only.
                from repro.dist import overlap_taps
                if pp:
                    rest = {k: v for k, v in params.items() if k != "blocks"}
                    params = {**overlap_taps(rest, cap_bytes=bucket_bytes),
                              "blocks": params["blocks"]}
                else:
                    params = overlap_taps(params, cap_bytes=bucket_bytes)
            return model.loss(params, batch)

        @jax.jit
        def step(params, opt_state, batch):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            if clip:
                gn = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                                  for g in jax.tree.leaves(grads)))
                scale = jnp.minimum(1.0, clip / (gn + 1e-9))
                grads = jax.tree.map(lambda g: g * scale.astype(g.dtype),
                                     grads)
            else:
                gn = jnp.zeros(())
            lr_scale = schedule(opt_state["step"])
            params, opt_state = optimizer.update(grads, opt_state, params,
                                                 lr_scale=lr_scale)
            return params, opt_state, {"loss": loss, "grad_norm": gn,
                                       **metrics}
        return step

    # -- explicit cross-worker sync (DESIGN.md §15) --------------------
    def _sync_setup(self):
        """``(mesh, waxes, n_workers)`` for the explicit sync path, or
        ``None`` when the ambient mesh cannot support it (no mesh, or a
        single gradient worker) — the caller degrades to the auto path."""
        from repro.dist import worker_axes
        from repro.dist import compat as dist_compat
        mesh = dist_compat.current_mesh()
        if mesh is None:
            return None
        waxes = worker_axes(mesh)
        sizes = dict(mesh.shape)
        n = 1
        for a in waxes:
            n *= sizes[a]
        if n <= 1:
            return None
        if sizes.get("model", 1) > 1:
            raise ValueError(
                "explicit sync_mode holds params replicated inside the "
                "per-worker region; a multi-way model axis is not supported")
        return mesh, waxes, n

    def _make_grad_fn(self, mesh, waxes):
        """Per-worker loss/grads as global ``(W, ...)`` arrays: params
        replicated into a fully-manual shard_map, batch split on dim 0
        over the worker axes, annotations suppressed (the pipeline-stage
        precedent — model code must not re-annotate inside manual)."""
        from jax.sharding import PartitionSpec as P
        from repro.dist import annotate as dist_annotate
        from repro.dist import compat as dist_compat
        model = self.model

        def per_worker(params, batch):
            with dist_annotate.suppressed():
                (loss, metrics), grads = jax.value_and_grad(
                    model.loss, has_aux=True)(params, batch)
            lead = lambda x: jnp.asarray(x)[None]
            return (lead(loss), jax.tree.map(lead, metrics),
                    jax.tree.map(lead, grads))

        return dist_compat.shard_map(
            per_worker, mesh,
            in_specs=(P(), P(waxes)),
            out_specs=(P(waxes), P(waxes), P(waxes)))

    def _finish_step(self, loss_w, metrics_w, grads, opt_state, params):
        """Shared tail of the explicit step: clip, schedule, update."""
        clip = self.tcfg.grad_clip
        if clip:
            gn = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                              for g in jax.tree.leaves(grads)))
            scale = jnp.minimum(1.0, clip / (gn + 1e-9))
            grads = jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)
        else:
            gn = jnp.zeros(())
        lr_scale = self.schedule(opt_state["step"])
        params, opt_state = self.optimizer.update(grads, opt_state, params,
                                                  lr_scale=lr_scale)
        metrics = {"loss": loss_w.mean(), "grad_norm": gn,
                   **jax.tree.map(lambda x: x.mean(axis=0), metrics_w)}
        return params, opt_state, metrics

    def _make_sequential_step(self, mesh, waxes, n_workers):
        from repro.dist import gradient_sync
        grad_fn = self._make_grad_fn(mesh, waxes)
        bucket_bytes = max(int(self.tcfg.bucket_mb * 2**20), 1)

        @jax.jit
        def step(params, opt_state, batch):
            loss_w, metrics_w, grads_w = grad_fn(params, batch)
            synced = gradient_sync(mesh, grads_w, mode="bucketed",
                                   bucket_bytes=bucket_bytes)
            grads = jax.tree.map(lambda g: g / n_workers, synced)
            return self._finish_step(loss_w, metrics_w, grads,
                                     opt_state, params)
        return step

    def _setup_eventual(self, mesh, waxes, n_workers, params):
        from repro.dist.collectives import EventualSync
        template = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct((n_workers,) + p.shape, p.dtype),
            params)
        self._ev = EventualSync(
            mesh, template, max_staleness=self.tcfg.max_staleness,
            bucket_bytes=max(int(self.tcfg.bucket_mb * 2**20), 1))
        self._ev_grad_fn = self._make_grad_fn(mesh, waxes)
        self._ev_n_workers = n_workers
        self._ev_steps = {}
        return self._ev.init_state()

    def _eventual_step(self, phase: int, warm: bool):
        """jit variant for one (phase, warm) — the schedule is static, so
        each variant lowers exactly the scheduled buckets' cross-pod
        collectives (what makes the HLO byte model exact)."""
        key = (phase, warm)
        if key not in self._ev_steps:
            ev, grad_fn = self._ev, self._ev_grad_fn
            n_workers = self._ev_n_workers

            @jax.jit
            def step(params, opt_state, batch, sync_state):
                loss_w, metrics_w, grads_w = grad_fn(params, batch)
                synced, new_state = ev.apply(grads_w, sync_state,
                                             phase=phase, warm=warm)
                grads = jax.tree.map(lambda g: g / n_workers, synced)
                out = self._finish_step(loss_w, metrics_w, grads,
                                        opt_state, params)
                return (*out, new_state)
            self._ev_steps[key] = step
        return self._ev_steps[key]

    def _make_globalize(self):
        """Batch host->device transfer.  Single-process: plain asarray.
        Multi-process (DESIGN.md §15): each host holds its contiguous
        row-slice of the global batch (``data.pipeline.global_batch_slice``
        order), which lines up with process-major device order on the
        ``(pod, data)`` mesh — ``make_array_from_process_local_data``
        assembles the global array with no cross-host shuffle."""
        if jax.process_count() == 1:
            return lambda b: {k: jnp.asarray(v) for k, v in b.items()}
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        from repro.dist import worker_axes
        from repro.dist import compat as dist_compat
        mesh = dist_compat.current_mesh()
        if mesh is None:
            raise ValueError("multi-process fit needs an ambient mesh "
                             "(jax.set_mesh) to place the global batch")
        sharding = NamedSharding(mesh, P(worker_axes(mesh)))
        nproc = jax.process_count()

        def to_global(v):
            v = np.asarray(v)
            gshape = (v.shape[0] * nproc,) + v.shape[1:]
            return jax.make_array_from_process_local_data(sharding, v,
                                                          gshape)
        return lambda b: {k: to_global(v) for k, v in b.items()}

    # ------------------------------------------------------------------
    def fit(self, data: Iterator, seed: int = 0, state=None,
            start_step: int = 0):
        """jit path.

        Per-step obs (DESIGN.md §11): ``data_wait`` / ``step`` /
        ``metrics_fetch`` / ``checkpoint`` spans on the "trainer" track.
        Metrics reach the host via ONE ``jax.device_get`` of the whole
        dict, only on log steps — per-item ``float(v)`` inside the loop
        forced a device sync per metric on every logged step, blocking
        dispatch of the next step's work.

        Checkpointing (DESIGN.md §12) is an *enqueue*: the span covers
        only the device->host shard snapshot; the write + atomic commit
        happen on the checkpointer's background thread and are flushed
        by ``wait_for_checkpoint()`` before fit returns.

        ``start_step`` resumes a run: pass the restored ``state`` and
        the step after the checkpoint's; the caller fast-forwards
        ``data`` to the same position.
        """
        params, opt_state = state or self.init_state(seed)
        mode = self.tcfg.sync_mode
        setup = self._sync_setup() if mode != "auto" else None
        sync_state = None
        if setup is None:
            # auto path — or explicit mode on a 1-worker mesh, where the
            # explicit reduction is the identity and GSPMD already agrees
            step_fn = self._make_step()
        elif mode == "sequential":
            step_fn = self._make_sequential_step(*setup)
        else:  # eventual
            sync_state = self._setup_eventual(*setup, params)
            step_fn = None
        rec = obs.get_recorder()
        globalize = self._make_globalize()
        t0 = time.time()
        t_log, i_log = t0, start_step    # steps_per_s window since last log
        data = iter(data)
        i = start_step
        while i < self.tcfg.total_steps:
            with rec.span("data_wait", cat="train", track="trainer", step=i):
                batch = next(data, None)
            if batch is None:
                break
            batch = globalize(batch)
            with rec.span("step", cat="train", track="trainer", step=i), \
                    rec.span("train_step", cat="train", track="trainer"):
                if step_fn is not None:
                    params, opt_state, metrics = step_fn(params, opt_state,
                                                         batch)
                else:
                    phase, warm = self._ev.phase_for(i)
                    params, opt_state, metrics, sync_state = \
                        self._eventual_step(phase, warm)(
                            params, opt_state, batch, sync_state)
                    self._ev.record_step(i)
            if i % self.tcfg.log_every == 0 or i == self.tcfg.total_steps - 1:
                with rec.span("metrics_fetch", cat="train", track="trainer",
                              step=i):
                    m = {k: float(v)
                         for k, v in jax.device_get(metrics).items()}
                now = time.time()
                m.update(step=i, wall_s=round(now - t0, 2),
                         steps_per_s=round((i - i_log + 1)
                                           / max(now - t_log, 1e-9), 3))
                t_log, i_log = now, i + 1
                self.history.append(m)
                self.logger.log(m)
                obs.get_metrics().gauge("train.steps_per_s").set(
                    m["steps_per_s"])
            if (self.tcfg.checkpoint_every
                    and i and i % self.tcfg.checkpoint_every == 0):
                with rec.span("checkpoint", cat="train", track="trainer",
                              step=i):
                    self.checkpointer.save(
                        {"params": params, "opt": opt_state}, step=i)
            i += 1
        if self.checkpointer is not None:
            with rec.span("checkpoint_wait", cat="train", track="trainer"):
                self.checkpointer.wait_for_checkpoint()
        return params, opt_state

    def wait_for_checkpoint(self):
        """Flush pending async checkpoint saves (re-raises failures)."""
        if self.checkpointer is not None:
            self.checkpointer.wait_for_checkpoint()

    # ------------------------------------------------------------------
    def fit_kvstore(self, data: Iterator, kv, n_workers: int = 1,
                    seed: int = 0):
        """The paper's KVStore loop: grads pushed, weights pulled.

        ``kv``: KVStoreDist (simulation). Each step splits the batch over
        n_workers; every worker pulls its (possibly stale) weights, computes
        grads, pushes. Returns the loss history.
        """
        params0, _ = self.init_state(seed)
        flat, treedef = jax.tree.flatten(params0)
        keys = [f"w{i}" for i in range(len(flat))]
        for k, v in zip(keys, flat):
            kv.init(k, np.asarray(v, np.float32))
        model = self.model

        @jax.jit
        def grad_fn(params, batch):
            (loss, _), grads = jax.value_and_grad(model.loss,
                                                  has_aux=True)(params, batch)
            return loss, grads

        losses = []
        lr = self.tcfg.lr
        kv.set_updater(lambda key, stored, g: stored - lr * np.asarray(g))
        for i, batch in enumerate(data):
            if i >= self.tcfg.total_steps:
                break
            tokens = np.asarray(batch["tokens"])
            shards = np.array_split(tokens, n_workers)
            step_losses = []
            for w in range(n_workers):
                pulled = [jnp.asarray(kv.pull(k, w)).astype(l.dtype)
                          for k, l in zip(keys, flat)]
                params = jax.tree.unflatten(treedef, pulled)
                loss, grads = grad_fn(params, {"tokens":
                                               jnp.asarray(shards[w])})
                gleaves = jax.tree.leaves(grads)
                for k, g in zip(keys, gleaves):
                    kv.push(k, w, np.asarray(g, np.float32) / n_workers)
                step_losses.append(float(loss))
            losses.append(float(np.mean(step_losses)))
        # per-key push/pull byte attribution -> process metrics registry
        kv.publish_metrics()
        return losses
