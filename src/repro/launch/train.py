"""Training launcher.

CPU smoke (reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --reduced --steps 50 --batch 8 --seq 64

Production (TPU pod; mesh built from the assignment's production shapes):
  python -m repro.launch.train --arch dbrx-132b --shape train_4k --mesh single
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import use_compile_cache
from repro.data import PrefetchIterator, SyntheticLM
from repro.models import reduced
from repro.train import TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family variant (CPU smoke)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--vocab", type=int, default=0,
                    help="override vocab (reduced runs)")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default="checkpoints",
                    help="run directory for sharded step_<n> checkpoints "
                         "(DESIGN.md §12)")
    ap.add_argument("--sync-checkpoint", action="store_true",
                    help="serialize checkpoints on the step critical path "
                         "instead of the async background writer")
    ap.add_argument("--resume", action="store_true",
                    help="restore params+opt from the latest committed "
                         "checkpoint in --checkpoint-dir (elastic: the "
                         "target mesh may differ from the saved one) and "
                         "continue from the next step")
    ap.add_argument("--init-from", metavar="CKPT", default=None,
                    help="warm-start params from a checkpoint directory "
                         "(optimizer state fresh, step 0)")
    ap.add_argument("--mesh", choices=["host", "single", "multi", "dist"],
                    default="host",
                    help="'dist' builds a (pod, data, model) mesh over all "
                         "processes of a jax.distributed job (DESIGN.md §15; "
                         "launch via repro.launch.multihost or set the "
                         "REPRO_COORDINATOR/... env addressing)")
    ap.add_argument("--sync-mode", choices=["auto", "sequential", "eventual"],
                    default="auto",
                    help="cross-worker gradient sync: GSPMD-implicit, "
                         "explicit two-level every step, or bounded-staleness "
                         "eventual consistency (DESIGN.md §15)")
    ap.add_argument("--max-staleness", type=int, default=0,
                    help="staleness bound (steps) for --sync-mode eventual")
    ap.add_argument("--overlap", action="store_true",
                    help="bucketed gradient sync emitted inside backward "
                         "(DESIGN.md §7); numerically identical")
    ap.add_argument("--bucket-mb", type=float, default=4.0,
                    help="bucket byte cap in MiB for --overlap")
    ap.add_argument("--seq-shard", action="store_true",
                    help="sequence-sharded batches: S stays sharded over "
                         "'model' and attention runs the ring schedule "
                         "(DESIGN.md §8); numerically identical")
    ap.add_argument("--attn-impl", choices=["auto", "dense", "ring"],
                    default="auto",
                    help="attention implementation selection "
                         "(PerfFlags.attn_impl)")
    ap.add_argument("--pp-stages", type=int, default=1,
                    help="pipeline stages over the super-block stack "
                         "(the 'stage' mesh axis; DESIGN.md §10)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="micro-batches streamed through the 1F1B "
                         "pipeline schedule (--pp-stages)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record engine/trainer spans and write a "
                         "Perfetto / chrome://tracing JSON (DESIGN.md §11)")
    ap.add_argument("--metrics", metavar="PATH", default=None,
                    help="write per-step records and the final metrics "
                         "snapshot as JSONL")
    args = ap.parse_args()
    use_compile_cache()

    from repro import obs
    if args.trace:
        obs.enable()

    if args.seq_shard or args.attn_impl != "auto":
        from repro.perf_flags import set_flags
        set_flags(seq_shard=args.seq_shard, attn_impl=args.attn_impl)

    cfg = get_config(args.arch)
    if args.reduced:
        over = {"vocab": args.vocab} if args.vocab else {}
        cfg = reduced(cfg, **over)

    if args.mesh == "dist":
        from repro.launch.mesh import (initialize_distributed,
                                       make_distributed_mesh)
        initialize_distributed()
        mesh = make_distributed_mesh()
        ctx = jax.set_mesh(mesh)
    elif args.mesh != "host":
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=args.mesh == "multi",
                                    pp_stages=args.pp_stages)
        ctx = jax.set_mesh(mesh)
    else:
        import contextlib
        ctx = contextlib.nullcontext()

    tcfg = TrainConfig(lr=args.lr, total_steps=args.steps,
                       warmup_steps=max(args.steps // 10, 1),
                       checkpoint_every=args.checkpoint_every,
                       checkpoint_dir=args.checkpoint_dir,
                       checkpoint_async=not args.sync_checkpoint,
                       grad_clip=5.0, overlap=args.overlap,
                       bucket_mb=args.bucket_mb,
                       pp_stages=args.pp_stages,
                       microbatches=args.microbatches,
                       sync_mode=args.sync_mode,
                       max_staleness=args.max_staleness)
    # per-host shard of the synthetic stream (identity single-process):
    # every host derives the same global batches and keeps its own rows
    data = PrefetchIterator(
        SyntheticLM(cfg.vocab, args.seq, args.batch, n_batches=args.steps,
                    process_index=jax.process_index(),
                    process_count=jax.process_count()),
        depth=4)
    logger = None
    if args.metrics:
        from repro.obs import JsonlSink, MetricsLogger, StdoutSink
        logger = MetricsLogger([StdoutSink(), JsonlSink(args.metrics)])
    with ctx:
        state, start_step = None, 0
        if args.resume:
            from repro.train import latest_checkpoint, load_checkpoint
            ck = latest_checkpoint(args.checkpoint_dir)
            if ck is None:
                raise SystemExit(f"--resume: no committed checkpoint under "
                                 f"{args.checkpoint_dir}")
            # elastic: restored onto the AMBIENT mesh's rule table, which
            # may differ from the mesh the checkpoint was saved under
            restored, step = load_checkpoint(ck)
            state, start_step = ((restored["params"], restored["opt"]),
                                 step + 1)
            print(f"resumed from {ck} (step {step})")
        elif args.init_from:
            from repro.train import load_checkpoint
            restored, _ = load_checkpoint(args.init_from)
            params = restored.get("params", restored)
            print(f"warm-start params from {args.init_from}")
        tr = Trainer(cfg, tcfg, logger=logger)
        if args.init_from and not args.resume:
            state = (params, tr.optimizer.init(params))
        it = iter(data)
        for _ in range(start_step):     # fast-forward the token stream
            next(it, None)
        tr.fit(it, state=state, start_step=start_step)
    print("final:", tr.history[-1])
    if logger is not None:
        logger.close()
    if args.metrics:
        obs.get_metrics().dump_jsonl(args.metrics)
        print(f"metrics: {args.metrics}")
    if args.trace:
        obs.export(args.trace)
        print(f"trace: {args.trace} (open in ui.perfetto.dev or "
              f"chrome://tracing)")


if __name__ == "__main__":
    main()
