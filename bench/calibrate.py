#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from: sound runs of the
program, the fp8 control in the program's place, and runs with a fault
planted in the timed path.  Many seeds run in one process, each a whole
cell run (set-up, a short window, the check), so set-up is paid once per
seed and compilation once.  The benchmark's own runs never run this.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 \
        --seconds 8 [--control] [--fault token|unchanged|half|exchange]

Prints one JSON line per seed, then the largest reading of each number
and, with ``--control``, the control's smallest and whether the control
came out as correct at the committed limits on any seed (it must not).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import common  # noqa: E402


def token_fault(engine):
    """Every sampled token altered where it is produced: the next id."""
    sample = engine._sample
    vocab = engine.cfg.vocab
    engine._sample = lambda logits: (sample(logits) + 1) % vocab


def unchanged_fault(trainer, mesh):
    """A step that returns its state unchanged."""
    step = trainer._make_step()

    def faulty(params, opt, batch):
        return params, opt, step(params, opt, batch)[2]
    return faulty


def half_fault(trainer, mesh):
    """Half of the batch left out, the mean taken over the rest."""
    step = trainer._make_step()

    def faulty(params, opt, batch):
        toks = batch["tokens"]
        return step(params, opt, {"tokens": toks[:toks.shape[0] // 2]})
    return faulty


def exchange_fault(trainer, mesh):
    """The exchange between chips left out: each chip takes the gradient
    of its own rows alone and steps its own copy of the state with it, so
    the copies drift apart (the harness reads chip 0's)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.dist import annotate
    if mesh is None or mesh.size < 2:
        raise common.BenchError("the exchange fault needs several chips")
    if trainer.tcfg.grad_clip:
        raise common.BenchError("the exchange fault does not clip")
    model, opt, schedule = trainer.model, trainer.optimizer, trainer.schedule

    def local(params, opt_state, batch):
        with annotate.suppressed():
            (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(
                params, batch)
        params, opt_state = opt.update(grads, opt_state, params,
                                       lr_scale=schedule(opt_state["step"]))
        return params, opt_state, {"loss": loss}

    rows = P(tuple(mesh.axis_names))
    return jax.jit(jax.shard_map(local, mesh=mesh,
                                 in_specs=(P(), P(), rows),
                                 out_specs=(P(), P(), P()), check_vma=False))


FAULTS = {"token": token_fault, "unchanged": unchanged_fault,
          "half": half_fault, "exchange": exchange_fault}


def calibrate(cell, seeds, seconds, control, fault, device, clock=None):
    from bench import serve_driver, train_driver
    clock = clock or common.Clock()
    cfg = common.arch_config(cell.config, cell.reference)
    driver = train_driver if cell.mix["kind"] == "train" else serve_driver
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        out = driver.drive(cell, cfg, seed, seconds, None, clock, t0,
                           fault=FAULTS[fault] if fault else None,
                           control=control)
        row = {"seed": seed, "correct": out["correct"],
               "checks": out.get("readings") or {
                   k: v["value"] for k, v in out["checks"].items()},
               "control": out["control"] if control else None,
               "control_correct": out["control_correct"],
               "metrics": out["metrics"],
               "memory_peak_bytes": out["memory_peak_bytes"],
               "run_s": time.perf_counter() - t0}
        print(json.dumps(row, default=float), flush=True)
        rows.append(row)
    summary = {"workload": cell.name, "fault": fault, "device": device,
               "seeds": seeds,
               "max": {k: max(r["checks"][k] for r in rows)
                       for k in rows[0]["checks"]}}
    if control:
        summary["control_min"] = {k: min(r["control"][k] for r in rows)
                                  for k in rows[0]["control"]}
        summary["control_correct_any"] = any(r["control_correct"]
                                             for r in rows)
    print(json.dumps(summary, default=float), flush=True)
    return rows, summary


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=sorted(FAULTS))
    args = ap.parse_args()
    cell = common.load_cell(args.workload)
    common.use_src_path()
    common.use_compile_cache()
    device = common.check_device(cell.chips)
    calibrate(cell, [int(s) for s in args.seeds.split(",")], args.seconds,
              args.control, args.fault, device)


if __name__ == "__main__":
    main()
