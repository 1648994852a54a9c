"""Driver of the ``train`` mix: the repo's ``Trainer`` and its jitted step,
one object built in set-up, driven from the seed through the steps the
check compares, then handed unchanged to the window.

``Trainer.fit`` is the window's call.  In the window it fetches the loss
every ``log_every`` steps, as a training job logs, so the host runs up to
that many steps ahead of the device and a short host stall does not idle
the chip.  The window holds every step dispatched before ``--seconds``
ran out and closes when the last of them has ended on the device, so the
rate covers all the work and all the time.  Every ``fit`` call reuses the
one compiled step.
"""
from __future__ import annotations

import dataclasses
import gc

import numpy as np

from . import common, traffic


class WindowFeed:
    """Batches for ``fit`` during the window: it opens the window at the
    first request and stops once ``seconds`` have passed."""

    def __init__(self, batches, clock, seconds, window):
        self.batches, self.clock = batches, clock
        self.seconds, self.window = seconds, window
        self.starts: list[float] = []      # host time each step was asked

    def __iter__(self):
        self.window.open()
        t1 = self.window.t0 + self.seconds
        while (now := self.clock.now()) < t1:
            self.starts.append(now)
            yield {"tokens": next(self.batches)}


def build(cfg, conf, chips: int):
    """The Trainer as the configuration states it, on one chip or on the
    ``(pod=1, data=chips, model=1)`` mesh with bucketed overlap sync."""
    import jax
    from repro.train import TrainConfig, Trainer
    t = conf["trainer"]
    tcfg = TrainConfig(lr=t["lr"], mu=t["momentum"],
                       weight_decay=t["weight_decay"],
                       warmup_steps=t["warmup_steps"],
                       total_steps=t["total_steps"], log_every=1,
                       grad_clip=t["grad_clip"], overlap=chips > 1,
                       bucket_mb=t.get("bucket_mb", 4.0))
    trainer = Trainer(cfg, tcfg)
    mesh = None
    if chips > 1:
        from repro.launch.mesh import (initialize_distributed,
                                       make_distributed_mesh)
        initialize_distributed()
        mesh = make_distributed_mesh()
        want = {"pod": 1, "data": chips, "model": 1}
        if dict(mesh.shape) != want:
            raise common.BenchError(f"mesh {dict(mesh.shape)}, want {want}")
    step = trainer._make_step()
    trainer._make_step = lambda: step     # every fit() call reuses it
    return trainer, mesh


def drive(cell, cfg, seed: int, seconds: float, trace_dir, clock,
          t_proc0: float, fault=None, control=False):
    """Set up, run the window, read memory, free the trainer, check the
    first steps against the reference.  ``fault(trainer, mesh)`` returns
    a broken step to run in place of the trainer's (calibration only)."""
    import contextlib

    import jax
    import jax.numpy as jnp
    from .reference import check
    conf, mix, ref, d = cell.config, cell.mix, cell.reference, cell.dims
    chips = cell.chips
    B = mix["batch_per_chip"] * chips
    S = mix["seq"]
    n_check = mix["check_steps"]
    trainer, mesh = build(cfg, conf, chips)
    if fault is not None:
        faulty = fault(trainer, mesh)
        trainer._make_step = lambda: faulty
    ctx = jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext()
    sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        sharding = NamedSharding(mesh, PartitionSpec())
    batches = traffic.train_batches(cfg.vocab, B, S, seed)
    first = [next(batches) for _ in range(n_check)]
    wd = conf["trainer"]["weight_decay"]
    with ctx:
        p0 = common.make_params(cfg, seed, ref, d, sharding)
        o0 = trainer.optimizer.init(p0)
        # step 1, then the first gradient from the momentum it left
        p1, o1 = trainer.fit(iter([{"tokens": first[0]}]), state=(p0, o0))
        g1 = jax.tree.map(lambda m, p: m - wd * p.astype(jnp.float32),
                          o1["mom"], p0)
        g_prog = check.leaf_norms(g1)
        del g1, p0, o0
        p, o = trainer.fit(iter([{"tokens": b} for b in first[1:]]),
                           state=(p1, o1), start_step=1)
        del p1, o1
        p0 = common.make_params(cfg, seed, ref, d, sharding)
        change_prog = check.leaf_norms(
            jax.tree.map(lambda a, b: a.astype(jnp.float32)
                         - b.astype(jnp.float32), p, p0))
        del p0
        losses_prog = [h["loss"] for h in trainer.history[:n_check]]
        if trace_dir is not None:
            # fit() holds the recorder it finds when it starts, so the
            # window's spans need it switched on before the call
            from repro import obs
            obs.enable(True)
        window = common.Window(clock, trace_dir)
        feed = WindowFeed(batches, clock, seconds, window)
        trainer.tcfg = dataclasses.replace(
            trainer.tcfg, log_every=conf["trainer"]["log_every"])
        p, o = trainer.fit(feed, state=(p, o), start_step=n_check)
        jax.block_until_ready((p, o))
        window.close()
    steps_in = len(feed.starts)
    metrics = {"train_tokens_per_s": steps_in * B * S / (window.t1
                                                         - window.t0),
               "setup_s": window.t0 - t_proc0}
    losses_win = [h["loss"] for h in trainer.history[n_check:]]
    failed = sum(1 for x in losses_win if not np.isfinite(x))
    mem = common.memory_peak_bytes(jax.devices()[:chips])
    spans = common.program_spans() if trace_dir is not None else None
    del p, o, trainer
    gc.collect()

    # the reference follows the first steps from the same weights, its
    # rows split over the cell's chips
    devices = jax.devices()[:chips]
    p0 = common.make_params(cfg, seed, ref, d, sharding)
    want = check.reference_steps(ref, p0, first, d, conf["trainer"],
                                 devices=devices)
    ctl = (check.reference_steps(ref, p0, first, d, conf["trainer"],
                                 quant="fp8", devices=devices)
           if control else None)
    del p0
    losses_ref, g_ref, change_ref = want
    moving = check.moving_leaves(g_ref)
    gaps = check.train_gaps((losses_prog, g_prog, change_prog), want,
                            moving)
    (loss_gap, _), (grad_gap, grad_at), (change_gap, change_at) = gaps
    lim = cell.limits
    checks = {"loss_rel_gap": {"value": loss_gap, "limit": lim["loss_rel_gap"]},
              "grad_leaf_gap": {"value": grad_gap,
                                "limit": lim["grad_leaf_gap"]},
              "change_leaf_gap": {"value": change_gap,
                                  "limit": lim["change_leaf_gap"]}}
    common.log(check_detail={"losses_prog": losses_prog,
                             "losses_ref": losses_ref,
                             "grad_worst_leaf": grad_at,
                             "change_worst_leaf": change_at,
                             "left_out": sorted(set(g_ref) - moving)})
    ctl_read = None if ctl is None else {
        k: v for k, (v, _) in zip(checks, check.train_gaps(ctl, want,
                                                           moving))}
    return {"metrics": metrics, "attempted": steps_in, "failed": failed,
            "memory_peak_bytes": mem, "checks": checks,
            "correct": common.within({k: c["value"]
                                      for k, c in checks.items()}, lim),
            "window": (window.t0, window.t1), "steps": feed.starts,
            "spans": spans, "train": {"tokens_per_step": B * S,
                                      "steps": steps_in, "chips": chips},
            "control": ctl_read,
            "control_correct": (None if ctl_read is None
                                else common.within(ctl_read, lim))}
