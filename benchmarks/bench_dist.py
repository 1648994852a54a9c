"""Flat vs hierarchical vs bucketed ``gradient_sync`` on a 2x4x2 host mesh
(§3.3 on-mesh + DESIGN.md §7): wall time per sync plus cross-pod all-reduce
bytes from the compiled HLO.

Bucketed mode additionally reports *per-bucket* cross-pod bytes (each
bucket lowered through the hierarchical schedule on its own) and
cross-validates them two ways:

* their sum must equal the monolithic ``hierarchical`` cross-pod total
  (no bytes appear or vanish when the sync is split for overlap);
* the analytic two-level KVStore counters, with one key per bucket, must
  attribute the same per-bucket traffic shares (``bytes_l2_by_key``) and
  keep the §3.3 level-1/level-2 ratio per key.

Eventual mode (DESIGN.md §15) cross-validates the bounded-staleness
schedule: each of the ``max_staleness + 1`` phase variants is lowered
separately and its compiled cross-pod all-reduce bytes must equal the
analytic ``eventual_crosspod_bytes`` model EXACTLY; the phases must sum
to the monolithic hierarchical total (every bucket still crosses the pod
boundary once per period), and the steady-state per-step mean must show
the ``period``× reduction.

Multi-device lowering needs --xla_force_host_platform_device_count set
before jax initializes, so the measurement runs in a subprocess and
reports one CSV row per (mode, metric).

Usage:  PYTHONPATH=src python benchmarks/bench_dist.py [--mode MODE]
        MODE in {flat, hier, bucketed, eventual, all} (default all)

CSV: name,value,derived
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")

# 8 workers x 1 MiB gradient (8 leaves x 128 KiB) on a 2 pods x 4 data x
# 2 model mesh; 256 KiB buckets -> 4 buckets of 2 leaves each
N_LEAVES = 8
LEAF_ELEMS = 32_768
N_ELEMS = N_LEAVES * LEAF_ELEMS          # 262144 floats = 1 MiB
BUCKET_BYTES = 256 * 1024
STEPS = 20
N_MACHINES, DEVS_PER_MACHINE = 2, 4      # = mesh (pod, data)
MAX_STALENESS = 2                        # eventual: 3-phase round robin

_BODY = f"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=16'
import time
import jax, jax.numpy as jnp, numpy as np
from repro.dist import make_mesh
from repro.dist.bucketing import BucketPlan
from repro.dist.collectives import gradient_sync
from repro.launch.dryrun import collective_bytes

MODES = os.environ['BENCH_DIST_MODES'].split(',')
mesh = make_mesh((2, 4, 2), ("pod", "data", "model"))
W = 8
rng = np.random.RandomState(0)
g = {{f"w{{i}}": jnp.asarray(rng.randn(W, {LEAF_ELEMS}), jnp.float32)
     for i in range({N_LEAVES})}}

with jax.set_mesh(mesh):
    for mode in [m for m in MODES if m != "eventual"]:
        f = jax.jit(lambda x, mode=mode: gradient_sync(
            mesh, x, mode=mode, bucket_bytes={BUCKET_BYTES}))
        coll = collective_bytes(f.lower(g).compile().as_text())
        out = f(g)                      # compile + warm
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range({STEPS}):
            out = f(g)
        jax.block_until_ready(out)
        us = (time.perf_counter() - t0) / {STEPS} * 1e6
        print(f"RESULT,{{mode}},us_per_sync,{{us:.1f}}")
        print(f"RESULT,{{mode}},crosspod_allreduce_bytes,"
              f"{{coll['raw']['all-reduce']}}")
        print(f"RESULT,{{mode}},total_collective_bytes,"
              f"{{coll['raw_total']}}")
    if "bucketed" in MODES:
        # per-bucket attribution: lower each bucket's buffer through the
        # hierarchical schedule on its own and read its cross-pod bytes
        leaves, _ = jax.tree.flatten(g)
        plan = BucketPlan.build(leaves, cap_bytes={BUCKET_BYTES},
                                lead_dims=1)
        buffers = plan.pack(leaves, lead_dims=1)
        print(f"RESULT,bucketed,n_buckets,{{plan.n_buckets}}")
        for i, (bucket, buf) in enumerate(zip(plan.buckets, buffers)):
            txt = jax.jit(lambda x: gradient_sync(
                mesh, [x], mode="hierarchical")).lower(buf).compile().as_text()
            coll = collective_bytes(txt)
            print(f"RESULT,bucketed,bucket{{i}}_crosspod_bytes,"
                  f"{{coll['raw']['all-reduce']}}")
            print(f"RESULT,bucketed,bucket{{i}}_payload_bytes,"
                  f"{{bucket.nbytes}}")
    if "eventual" in MODES:
        # bounded-staleness schedule: lower every phase variant and read
        # its cross-pod all-reduce bytes off the compiled HLO
        from repro.dist.collectives import EventualSync
        ev = EventualSync(mesh, g, max_staleness={MAX_STALENESS},
                          bucket_bytes={BUCKET_BYTES})
        state = ev.init_state()
        print(f"RESULT,eventual,n_buckets,{{ev.n_buckets}}")
        print(f"RESULT,eventual,period,{{ev.period}}")
        print(f"RESULT,eventual,state_bytes_per_worker,"
              f"{{ev.state_bytes()['per_worker']}}")
        variants = [(p, False) for p in range(ev.period)] + [(0, True)]
        total_us = 0.0
        for phase, warm in variants:
            f = jax.jit(lambda x, s, phase=phase, warm=warm:
                        ev.apply(x, s, phase=phase, warm=warm))
            coll = collective_bytes(f.lower(g, state).compile().as_text())
            tag = "warm" if warm else f"phase{{phase}}"
            print(f"RESULT,eventual,{{tag}}_crosspod_bytes,"
                  f"{{coll['raw']['all-reduce']}}")
            print(f"RESULT,eventual,{{tag}}_crosspod_bytes_analytic,"
                  f"{{ev.crosspod_allreduce_bytes(phase, warm=warm)}}")
            out, st = f(g, state)       # compile + warm
            jax.block_until_ready((out, st))
            if not warm:
                t0 = time.perf_counter()
                for _ in range({STEPS}):
                    out, st = f(g, st)
                jax.block_until_ready((out, st))
                total_us += (time.perf_counter() - t0) / {STEPS} * 1e6
        print(f"RESULT,eventual,us_per_sync,{{total_us / ev.period:.1f}}")
        steady = sum(ev.crosspod_allreduce_bytes(p) for p in
                     range(ev.period)) / ev.period
        print(f"RESULT,eventual,crosspod_allreduce_bytes,{{steady:.1f}}")
"""

_MODE_SETS = {
    "flat": ["flat"],
    "hier": ["hierarchical"],
    # bucketed/eventual need the monolithic hierarchical total as their
    # reference
    "bucketed": ["hierarchical", "bucketed"],
    "eventual": ["hierarchical", "eventual"],
    "all": ["flat", "hierarchical", "bucketed", "eventual"],
}


def _measure(mode: str = "all") -> dict:
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               BENCH_DIST_MODES=",".join(_MODE_SETS[mode]))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _BODY], capture_output=True,
                       text=True, env=env, timeout=560)
    if r.returncode != 0:
        raise RuntimeError(f"bench_dist subprocess failed:\n{r.stderr[-2000:]}")
    out = {}
    for line in r.stdout.splitlines():
        if line.startswith("RESULT,"):
            _, m, metric, value = line.split(",")
            out[(m, metric)] = float(value)
    return out


def _analytic_bucket_shares(vals) -> tuple[dict[int, float], dict[int, float]]:
    """Per-bucket ``(l2_shares, l1_over_l2_ratios)`` from the two-level
    KVStore byte counters (one key per bucket) — the analytic side of the
    bucketed cross-validation."""
    from repro.core import KVStoreDist
    import numpy as np
    kv = KVStoreDist(n_machines=N_MACHINES,
                     devices_per_machine=DEVS_PER_MACHINE,
                     consistency="sequential")
    n = int(vals[("bucketed", "n_buckets")])
    for i in range(n):
        elems = int(vals[("bucketed", f"bucket{i}_payload_bytes")]) // 4
        kv.init(f"bucket{i}", np.zeros(elems, np.float32))
    for w in range(N_MACHINES * DEVS_PER_MACHINE):
        for i in range(n):
            elems = int(vals[("bucketed", f"bucket{i}_payload_bytes")]) // 4
            kv.push(f"bucket{i}", worker=w,
                    grad=np.ones(elems, np.float32))
    total = sum(kv.bytes_l2_by_key.values())
    shares = {i: kv.bytes_l2_by_key[f"bucket{i}"] / total for i in range(n)}
    # §3.3 two-level ratio per bucket, reported as rows so validate() can
    # fail it structurally rather than crashing mid-benchmark
    ratios = {i: kv.bytes_l1_by_key[f"bucket{i}"]
              / max(kv.bytes_l2_by_key[f"bucket{i}"], 1) for i in range(n)}
    return shares, ratios


def run(csv: bool = True, mode: str = "all"):
    vals = _measure(mode)
    rows = []
    for (m, metric), value in sorted(vals.items()):
        derived = ""
        if metric == "crosspod_allreduce_bytes" and m == "hierarchical":
            flat = vals.get(("flat", metric))
            if flat:
                derived = f"{flat / max(value, 1):.1f}x fewer than flat"
        if metric == "crosspod_allreduce_bytes" and m == "eventual":
            hier = vals.get(("hierarchical", metric))
            if hier:
                derived = (f"{hier / max(value, 1):.1f}x fewer than "
                           f"sequential (steady state)")
        rows.append((f"gradient_sync_{m}_{metric}", value, derived))
        if csv:
            print(f"{rows[-1][0]},{value},{derived}")
    if ("bucketed", "n_buckets") in vals:
        shares, ratios = _analytic_bucket_shares(vals)
        for i, share in shares.items():
            rows.append((f"gradient_sync_bucketed_bucket{i}_l2_share_analytic",
                         share, "KVStore bytes_l2_by_key"))
            if csv:
                print(f"{rows[-1][0]},{share},{rows[-1][2]}")
        for i, ratio in ratios.items():
            rows.append((f"gradient_sync_bucketed_bucket{i}_l1_over_l2",
                         ratio, "analytic two-level ratio"))
            if csv:
                print(f"{rows[-1][0]},{ratio},{rows[-1][2]}")
    return rows


def validate(rows, mode: str = "all") -> list[str]:
    """§3.3 on-mesh: hierarchical moves fewer cross-pod bytes than flat;
    DESIGN.md §7: the per-bucket bytes sum back to the monolithic
    hierarchical total and match the analytic KVStore attribution.

    ``mode`` declares which measurements are *required*: every sync mode
    the run was supposed to measure must report nonzero cross-pod bytes
    (a parser that silently reads 0 is a failure, not a pass)."""
    d = {name: value for name, value, _ in rows}
    failures = []
    flat = d.get("gradient_sync_flat_crosspod_allreduce_bytes", 0)
    hier = d.get("gradient_sync_hierarchical_crosspod_allreduce_bytes", 0)
    for required in _MODE_SETS[mode]:
        if not d.get(f"gradient_sync_{required}_crosspod_allreduce_bytes", 0):
            failures.append(
                f"missing/zero {required} gradient_sync byte measurement")
    if flat and hier:
        if hier >= flat:
            failures.append(
                f"hierarchical all-reduce bytes {hier} >= flat {flat}")
        elif flat / hier < 2.0:
            failures.append(
                f"hierarchical reduction factor {flat / hier:.2f} < 2.0")

    n = int(d.get("gradient_sync_bucketed_n_buckets", 0))
    if n:
        if n < 2:
            failures.append(f"expected a multi-bucket plan, got {n} buckets")
        per_bucket = [d[f"gradient_sync_bucketed_bucket{i}_crosspod_bytes"]
                      for i in range(n)]
        if sum(per_bucket) != hier:
            failures.append(
                f"per-bucket cross-pod bytes {per_bucket} sum to "
                f"{sum(per_bucket)}, monolithic hierarchical moved {hier}")
        hlo_total = sum(per_bucket)
        for i in range(n):
            analytic = d[f"gradient_sync_bucketed_bucket{i}_l2_share_analytic"]
            hlo_share = per_bucket[i] / hlo_total
            if abs(analytic - hlo_share) > 1e-9:
                failures.append(
                    f"bucket {i}: analytic l2 share {analytic} != HLO share "
                    f"{hlo_share}")
            ratio = d.get(f"gradient_sync_bucketed_bucket{i}_l1_over_l2", 0)
            if ratio != DEVS_PER_MACHINE:
                failures.append(
                    f"bucket {i}: analytic l1/l2 ratio {ratio} != "
                    f"devices-per-machine {DEVS_PER_MACHINE}")

    period = int(d.get("gradient_sync_eventual_period", 0))
    if period:
        # the eventual gate (DESIGN.md §15): per-phase compiled bytes ==
        # the analytic staleness model EXACTLY, phases sum to the
        # sequential (hierarchical) total, warm == full sync, steady-state
        # mean shows the period-x reduction
        if period != MAX_STALENESS + 1:
            failures.append(f"eventual period {period} != "
                            f"max_staleness+1 = {MAX_STALENESS + 1}")
        phase_bytes = []
        for p in range(period):
            hlo = d.get(f"gradient_sync_eventual_phase{p}_crosspod_bytes")
            analytic = d.get(
                f"gradient_sync_eventual_phase{p}_crosspod_bytes_analytic")
            if hlo is None or hlo != analytic:
                failures.append(
                    f"eventual phase {p}: HLO cross-pod bytes {hlo} != "
                    f"analytic model {analytic}")
            phase_bytes.append(hlo or 0)
        warm = d.get("gradient_sync_eventual_warm_crosspod_bytes", 0)
        warm_an = d.get("gradient_sync_eventual_warm_crosspod_bytes_analytic")
        if warm != warm_an:
            failures.append(f"eventual warm: HLO bytes {warm} != "
                            f"analytic {warm_an}")
        if hier and sum(phase_bytes) != hier:
            failures.append(
                f"eventual phases {phase_bytes} sum to {sum(phase_bytes)}, "
                f"sequential hierarchical moved {hier}")
        if hier and warm != hier:
            failures.append(f"eventual warm sync {warm} != hierarchical "
                            f"full sync {hier}")
        steady = d.get("gradient_sync_eventual_crosspod_allreduce_bytes", 0)
        if hier and abs(steady - hier / period) > 1:
            failures.append(
                f"eventual steady-state mean {steady} != hierarchical/"
                f"period = {hier / period:.1f}")
    return failures


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=sorted(_MODE_SETS), default="all")
    args = ap.parse_args()
    rows = run(mode=args.mode)
    bad = validate(rows, mode=args.mode)
    print("PASS" if not bad else bad)
    sys.exit(1 if bad else 0)
