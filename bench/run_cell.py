#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run_cell.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is found by name in ``BENCHMARK.json``; its configuration, mix,
limits and per-layer readers are files under ``bench/`` found by the
names there.  Set-up makes the weights on the device from the seed and
warms the cell's own shapes; the window then runs ``--seconds``.  With
``--trace 0`` the result line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a profiler trace of the window.
After the window the program's state is freed and what the window
produced is compared with the plain reference (``bench/reference``).

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``checks``); each
number compared is also printed beside its limit as the last lines of
standard error.  With no TPU, fewer chips than the cell needs, or a
device missing from ``bench/peaks.json``, it exits nonzero and prints no
result line.
"""
from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import common, trace_reduce  # noqa: E402

TRACE_DIR = common.ROOT / ".bench_trace"


@dataclass
class ReadCtx:
    """What a per-layer reader may read: the cell, the reference's sizes,
    the device's peaks, the window on both clocks, the harness's record of
    each step, the program's host spans and the reduced device trace."""
    cell: object
    dims: dict
    peaks: dict
    chips: int
    window: tuple          # host clock, seconds
    dev_window: tuple      # trace clock, ns
    steps: list
    spans: list            # program spans: (start_s, end_s, name, args)
    trace: object
    run: dict              # the driver's other outputs

    def steps_in_window(self):
        w0, w1 = self.window
        return [s for s in self.steps if s.t0 >= w0 and s.t1 <= w1]

    def spans_named(self, name):
        w0, w1 = self.window
        return [s for s in self.spans if s[2] == name and s[0] >= w0
                and s[1] <= w1]


def load_reader(name: str):
    return common.load_module(common.BENCH / "metrics" / f"{name}.py",
                              "bench_metric_").read


def per_layer(cell, run, dims, peaks, trace_dir) -> tuple[dict, dict, dict]:
    """Per-layer metrics, the device's busy and window seconds, and the
    breakdown, from the traced window."""
    trace = trace_reduce.load(trace_dir)
    w0, w1 = run["window"]
    spans = [(e["t_abs"], e["t_abs"] + e.get("dur", 0.0) / 1e6, e["name"],
              e.get("args", {})) for e in run["spans"] or []
             if e.get("ph") == "X"]
    if cell.mix["kind"] == "train":
        ann = "train_step"
        starts = [s[0] for s in spans if s[2] == ann]
    else:
        ann = "bench_step"
        starts = [s.t0 for s in run["steps"]]
    starts = [t for t in starts if w0 <= t <= w1]
    off = trace_reduce.clock_offset_ns(trace, ann, starts)
    t0, t1 = w0 * 1e9 + off, w1 * 1e9 + off
    ctx = ReadCtx(cell=cell, dims=dims, peaks=peaks, chips=cell.chips,
                  window=(w0, w1), dev_window=(t0, t1),
                  steps=run["steps"], spans=spans, trace=trace, run=run)
    metrics = {}
    for m in cell.per_layer:
        val = load_reader(m["name"])(ctx)
        if val is not None:
            metrics[m["name"]] = {"value": val["value"], "unit": m["unit"],
                                  **{k: v for k, v in val.items()
                                     if k not in ("value", "unit")}}
    busy = trace_reduce.busy_s(trace, t0, t1)
    host = [(a * 1e9 + off, b * 1e9 + off, n) for a, b, n, _ in spans
            if n in HOST_LABELS]
    host += [(s, e, n) for s, e, n in trace.host if n in HOST_LABELS]
    breakdown = {"device_ops": trace_reduce.top_ops(trace, t0, t1),
                 "idle_gaps": trace_reduce.idle_gaps(trace, t0, t1, host)}
    return metrics, {"busy_s": busy, "window_s": (t1 - t0) / 1e9}, breakdown


# host spans that label idle gaps: the harness's step annotation and the
# program's own spans inside it
HOST_LABELS = {"bench_step", "train_step", "prefill_chunk", "decode_step",
               "data_wait", "step", "metrics_fetch"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = common.load_cell(args.workload)
        common.use_src_path()
        cache = common.use_compile_cache()
        device = common.check_device(cell.chips)
    except (common.BenchError, FileNotFoundError, ImportError) as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2
    common.log(workload=args.workload, seed=args.seed, seconds=args.seconds,
               trace=args.trace, compile_cache=cache, device=device)
    return run(cell, args, device)


def run(cell, args, device, clock=None) -> int:
    from bench import serve_driver, train_driver
    clock = clock or common.Clock()
    cfg = common.arch_config(cell.config, cell.reference)
    dims = cell.dims
    trace_dir = None
    if args.trace:
        # one trace at a time, kept until the next traced run
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = TRACE_DIR
    driver = train_driver if cell.mix["kind"] == "train" else serve_driver
    out = driver.drive(cell, cfg, args.seed, args.seconds, trace_dir, clock,
                       T_PROC0)
    common.log(peak_bytes_in_use=out["memory_peak_bytes"],
               window_s=out["window"][1] - out["window"][0])
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    breakdown = None
    if args.trace:
        metrics, busy, breakdown = per_layer(
            cell, out, dims, common.peaks_for(device["kind"]), trace_dir)
        device.update(busy)
    else:
        names = [m["name"] for m in cell.end_to_end]
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": out["metrics"][k], "unit": units[k]}
                   for k in names}
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(common.result_line(correct=out["correct"],
                             attempted=out["attempted"],
                             failed=out["failed"], metrics=metrics,
                             device=device, checks=out["checks"],
                             breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
