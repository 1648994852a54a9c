"""The four-chip data-parallel training cell rehearsed on four virtual CPU
devices, at ``reduced()`` size: a plain and a traced run through
``run_cell.run``, the same run with each fault a data-parallel step can
have, and the reference's rows split over the devices against one
device.  Prints one JSON line; ``test_dp4.py`` runs it in a process of
its own, since the device count is fixed before JAX starts.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python -m bench.tests.dp4_rehearsal
"""
from __future__ import annotations

import contextlib
import io
import json
import re
import sys
import tempfile
from pathlib import Path

from bench import calibrate, common, run_cell, trace_reduce
from bench.tests import tiny

WORKLOAD = "qwen1.5-0.5b.train-4k-dp4"
FAULTS = ("exchange", "half", "unchanged")
# the CPU client's executor and Eigen threads record each HLO op they run
# as a host event named for the op (``all-reduce.3``, ``dot.12``)
CPU_EXECUTOR = "tf_XLA"
OP_NAME = re.compile(r"[a-z][a-z0-9_.\-]*")
LOAD = trace_reduce.load


def cpu_devices(path):
    """The trace with each CPU thread that runs HLO ops standing in for a
    device: a CPU trace has no device plane."""
    from jax.profiler import ProfileData
    t = LOAD(path)
    if t.devices:
        return t
    pd = ProfileData.from_file(trace_reduce.find_xplane(path))
    for plane in pd.planes:
        for ln in plane.lines:
            if not ln.name.startswith(CPU_EXECUTOR):
                continue
            ev = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                         e.name) for e in ln.events
                        if OP_NAME.fullmatch(e.name))
            if ev:
                t.devices.append(trace_reduce.DeviceOps(
                    ln.name, ev, busy=trace_reduce.merge(
                        (s, e) for s, e, _ in ev)))
    return t


def _run(cell, trace):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run_cell.run(cell, tiny.args(WORKLOAD, trace=trace),
                          {"platform": "cpu", "kind": "cpu", "count": 4})
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def _reference_split_over_devices(cell):
    """The first steps' readings with the rows split over four devices and
    on one: the same numbers to f32 rounding."""
    import jax
    import numpy as np
    from bench import traffic
    from bench.reference import check
    cfg = common.arch_config(cell.config, cell.reference)
    params = common.make_params(cfg, 5, cell.reference, cell.dims)
    batches = [np.asarray(b) for b, _ in zip(
        traffic.train_batches(cfg.vocab, 8, 64, 5), range(3))]
    tr = cell.config["trainer"]
    one = check.reference_steps(cell.reference, params, batches, cell.dims,
                                tr, devices=jax.devices()[:1])
    four = check.reference_steps(cell.reference, params, batches, cell.dims,
                                 tr, devices=jax.devices()[:4])
    moving = check.moving_leaves(one[1])
    gaps = check.train_gaps(four, one, moving)
    return {k: v for k, (v, _) in zip(("loss", "grad", "change"), gaps)}


def main() -> None:
    import jax
    common.use_src_path()
    common.peaks_for = lambda kind: {"bf16_flops_per_s": 1.0,
                                     "hbm_bytes_per_s": 1.0}
    trace_reduce.load = cpu_devices
    cell = tiny.tiny_cell(WORKLOAD)
    with tempfile.TemporaryDirectory() as tmp:
        run_cell.TRACE_DIR = Path(tmp) / "trace"
        plain, traced = _run(cell, 0), _run(cell, 1)
    faults = {}
    with contextlib.redirect_stdout(io.StringIO()):
        for f in FAULTS:
            rows, _ = calibrate.calibrate(cell, [21], 2.0, False, f,
                                          {"platform": "cpu"})
            faults[f] = {"correct": rows[0]["correct"],
                         "checks": rows[0]["checks"]}
    print(json.dumps({"devices": len(jax.devices()), "plain": plain,
                      "traced": traced, "faults": faults,
                      "split_reference": _reference_split_over_devices(cell)},
                     default=float), flush=True)


if __name__ == "__main__":
    sys.exit(main())
