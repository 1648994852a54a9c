#!/usr/bin/env python3
"""Rate sweep of an open-loop serving cell: one engine, set up once, then
each offered rate in turn through the cell's own open-loop driver.  Used
once to find the knee (the highest rate the system sustains without a
growing queue) whose four fifths the cell's mix file then fixes as a
number.  The benchmark's own runs never run this.

    python3 bench/sweep.py --workload qwen1.5-0.5b.chat --rates 6,10,14 \
        --seconds 20 --seed 1
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import common  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--warm", type=float, help="warm-up seconds per rate")
    args = ap.parse_args()
    cell = common.load_cell(args.workload)
    common.use_src_path()
    common.use_compile_cache()
    device = common.check_device(cell.chips)
    from bench import serve_driver, traffic
    clock = common.Clock()
    cfg = common.arch_config(cell.config, cell.reference)
    params = common.make_params(cfg, args.seed, cell.reference, cell.dims)
    engine, _ = serve_driver.build_engine(cfg, cell.config, cell.mix, params,
                                          args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = copy.deepcopy(cell.mix)
        mix["rate_per_s"] = rate
        if args.warm is not None:
            mix["warm_s"] = args.warm
        reqs = traffic.make_requests(mix, cfg.vocab, args.seed,
                                     args.seconds)
        window = common.Window(clock)
        depth = []
        tr, in_window, late = serve_driver.run_open_loop(
            engine, reqs, mix, args.seconds, clock, window,
            step_hook=lambda: depth.append(len(engine.pending)))
        m, attempted, failed = serve_driver.serve_metrics(
            tr, window, in_window, "open_loop")
        recs = [tr.recs[i] for i in in_window if i in tr.recs]
        # of the window's requests that reached a first token in it
        ttft = [1e3 * (r.t_first - r.due) for r in recs if r.t_first]
        gaps = [1e3 * g for t, g in tr.gaps
                if window.t0 < t <= window.t1]
        steps = [s for s in tr.steps
                 if window.t0 <= s.t0 and s.t1 <= window.t1]
        lanes = [len(s.decode_ctx) for s in steps]
        print(json.dumps({
            "rate_per_s": rate, "attempted": attempted, "failed": failed,
            "output_tokens_per_s": m["output_tokens_per_s"],
            "ttft_ms": {"p50": serve_driver._pct(ttft, 50),
                        "p95": serve_driver._pct(ttft, 95)},
            "tpot_ms": {"p50": serve_driver._pct(gaps, 50),
                        "p95": m["tpot_p95_ms"]},
            "late_ms_p95": 1e3 * serve_driver._pct(late, 95),
            "queue_depth": {"first": depth[0] if depth else 0,
                            "max": max(depth, default=0),
                            "last": depth[-1] if depth else 0},
            "decode_lanes_mean": sum(lanes) / max(len(lanes), 1),
            "step_ms_mean": 1e3 * sum(s.t1 - s.t0 for s in steps)
            / max(len(steps), 1),
            "device": device}), flush=True)
        engine.reset()


if __name__ == "__main__":
    main()
