"""Serving launcher: batched generation with the static or paged engine.

CPU smoke:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
      --batch 4 --new-tokens 16
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
      --paged --block-size 16 --max-batch 4 --mixed --batch 12
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import get_model, reduced
from repro.serve import PagedServeEngine, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests (paged: queue size; static: "
                         "one lockstep batch)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=None,
                    help="sample from the k highest logits only (fused "
                         "Pallas sampling kernel; needs --temperature > 0)")
    ap.add_argument("--top-p", type=float, default=None,
                    help="nucleus sampling mass cutoff (fused kernel; "
                         "needs --temperature > 0)")
    ap.add_argument("--kv-dtype",
                    choices=["native", "int8", "fp8_e4m3", "fp8_e5m2"],
                    default="native",
                    help="paged KV-cache storage dtype; sub-byte-accurate "
                         "per-row scales ride alongside the pools "
                         "(DESIGN.md §13; paged engine only)")
    ap.add_argument("--init-from", metavar="CKPT", default=None,
                    help="load params from a (possibly differently-"
                         "sharded) training checkpoint directory instead "
                         "of random init — the train->serve handoff "
                         "(DESIGN.md §12).  Accepts a run dir of step_<n> "
                         "checkpoints (latest wins) or one checkpoint dir")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV-cache + continuous batching "
                         "(DESIGN.md §9)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged cache block size in tokens")
    ap.add_argument("--max-batch", type=int, default=0,
                    help="decode lanes for the paged engine "
                         "(default: --batch)")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="prompt tokens prefilled per engine step (paged)")
    ap.add_argument("--mixed", action="store_true",
                    help="draw per-request prompt lengths from "
                         "[prompt_len/4, prompt_len] and uneven token "
                         "budgets (the continuous-batching workload)")
    ap.add_argument("--seq-shard", action="store_true",
                    help="sequence-sharded prefill: S over 'model', ring "
                         "attention for full layers (DESIGN.md §8)")
    ap.add_argument("--attn-impl", choices=["auto", "dense", "ring"],
                    default="auto",
                    help="attention implementation selection "
                         "(PerfFlags.attn_impl)")
    ap.add_argument("--admission", choices=["reserve", "optimistic"],
                    default="reserve",
                    help="paged admission: 'reserve' holds worst-case "
                         "blocks per request; 'optimistic' admits on "
                         "prompt fit and preempts on pressure "
                         "(DESIGN.md §14)")
    ap.add_argument("--swap-blocks", type=int, default=0,
                    help="host swap pool capacity in block-equivalents "
                         "(preempted lanes swap KV there; 0 = recompute-"
                         "only preemption)")
    ap.add_argument("--victim-policy",
                    choices=["lowest_priority", "most_blocks", "lifo"],
                    default="lowest_priority",
                    help="which lane preemption evicts first")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline; expired requests end "
                         "TIMEOUT with resources reclaimed (paged)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded admission queue; overflow is shed with "
                         "a typed rejection, never an exception (paged)")
    ap.add_argument("--shed-policy",
                    choices=["reject_newest", "evict_lowest"],
                    default="reject_newest",
                    help="what a full queue does to the newest arrival")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record per-request lifecycle spans and write a "
                         "Perfetto / chrome://tracing JSON (DESIGN.md §11)")
    ap.add_argument("--metrics", metavar="PATH", default=None,
                    help="write the final metrics snapshot (TTFT/TPOT/"
                         "queue-wait histograms, pool gauges) as JSONL")
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
        cfg = reduced(cfg)
    model = get_model(cfg)
    if args.init_from:
        from repro.train import latest_checkpoint, load_checkpoint
        ck = latest_checkpoint(args.init_from) or args.init_from
        restored, step = load_checkpoint(ck)
        params = restored.get("params", restored)
        print(f"params from {ck} (step {step})")
    else:
        params = model.init(jax.random.PRNGKey(0))
    max_len = args.prompt_len + args.new_tokens + 8

    rng = np.random.RandomState(0)
    if args.mixed:
        lens = rng.randint(max(1, args.prompt_len // 4),
                           args.prompt_len + 1, args.batch)
        budgets = list(rng.randint(max(1, args.new_tokens // 4),
                                   args.new_tokens + 1, args.batch))
    else:
        lens = [args.prompt_len] * args.batch
        budgets = [args.new_tokens] * args.batch
    prompts = [list(rng.randint(1, cfg.vocab, L)) for L in lens]
    extra = {}
    if cfg.encoder_layers:
        extra["frames"] = np.asarray(rng.randn(
            args.batch, cfg.frontend_tokens, cfg.frontend_dim), np.float32)
    elif cfg.frontend_tokens:
        extra["patches"] = np.asarray(rng.randn(
            args.batch, cfg.frontend_tokens, cfg.frontend_dim), np.float32)

    if args.paged:
        eng = PagedServeEngine(cfg, params, block_size=args.block_size,
                               max_batch=args.max_batch or args.batch,
                               max_len=max_len,
                               prefill_chunk=args.prefill_chunk,
                               kv_dtype=args.kv_dtype,
                               top_k=args.top_k, top_p=args.top_p,
                               admission=args.admission,
                               swap_blocks=args.swap_blocks,
                               victim_policy=args.victim_policy,
                               max_queue=args.max_queue,
                               shed_policy=args.shed_policy)
        deadlines = ([args.deadline_ms] * len(prompts)
                     if args.deadline_ms is not None else None)
        outs, stats = eng.generate(prompts, max_new_tokens=budgets,
                                   temperature=args.temperature,
                                   deadlines_ms=deadlines)
        by = {}
        for res in eng.results.values():
            by[res.status.value] = by.get(res.status.value, 0) + 1
        print(f"generated: {len(outs)} requests, "
              f"{sum(len(o) for o in outs)} tokens, "
              f"peak cache blocks {stats.peak_cache_blocks} "
              f"({stats.peak_cache_bytes / 2**20:.2f} MiB)")
        print(f"lifecycle: {by} | preempted {stats.preempted} "
              f"restored {stats.restored} shed {stats.shed} "
              f"timeouts {stats.timeouts} | swap peak "
              f"{stats.swap_peak_blocks} blocks | goodput "
              f"{stats.goodput_tok_per_s:.1f} tok/s")
        print(f"latency: ttft p50 {stats.ttft_p50 * 1e3:.1f}ms "
              f"p99 {stats.ttft_p99 * 1e3:.1f}ms | "
              f"tpot p50 {stats.tpot_p50 * 1e3:.2f}ms "
              f"p99 {stats.tpot_p99 * 1e3:.2f}ms | "
              f"queue wait p50 {stats.queue_wait_p50 * 1e3:.1f}ms "
              f"p99 {stats.queue_wait_p99 * 1e3:.1f}ms")
    else:
        if args.kv_dtype != "native":
            ap.error("--kv-dtype applies to the paged engine (--paged)")
        eng = ServeEngine(cfg, params, max_len=max_len)
        toks, stats = eng.generate(prompts,
                                   max_new_tokens=max(budgets),
                                   temperature=args.temperature,
                                   top_k=args.top_k, top_p=args.top_p,
                                   extra_inputs=extra)
        print("generated:", toks.shape)
    print(f"compile {stats.compile_s:.3f}s prefill {stats.prefill_s:.3f}s "
          f"decode {stats.decode_s:.3f}s ({stats.tok_per_s:.1f} tok/s)")
    if args.metrics:
        obs.get_metrics().dump_jsonl(args.metrics)
        print(f"metrics: {args.metrics}")
    if args.trace:
        obs.export(args.trace)
        print(f"trace: {args.trace} (open in ui.perfetto.dev or "
              f"chrome://tracing)")


if __name__ == "__main__":
    main()
