"""Pipeline parallelism over the super-block stack (DESIGN.md §10): the
1F1B stage schedule's analytic collective-permute byte model
cross-validated against the compiled HLO — the same HLO-vs-model
discipline ``bench_dist.py``/``bench_ring.py`` established — plus the
ISSUE-5 acceptance parity gate: pipelined loss and parameter gradients
must match the single-stage baseline to 1e-5.

For each stage count pp in {1, 2, 4} (one mesh axis, "stage"; reduced
dense config with n_super = 4, M = 4 microbatches):

* lower + compile the model loss (fwd) and its parameter grad on the
  stage mesh with ``PerfFlags.pp_stages/microbatches`` set;
* parse collective-permute bytes out of the compiled HLO and require
  them to equal ``pipeline_permute_bytes`` *exactly* — forward
  ``(M + pp - 2)`` hops of one ``(b, S, D)`` microbatch activation, the
  reverse schedule the same count of activation-cotangent hops (pp = 1
  takes the plain unpipelined stack: zero permutes);
* run the pipelined loss/grad numerically and compare against the
  unpipelined no-mesh baseline (max abs diff, gated at 1e-5).

Multi-device lowering needs --xla_force_host_platform_device_count
before jax initializes, so measurement runs in a subprocess (CSV rows
out).

Usage:  PYTHONPATH=src python benchmarks/bench_pipeline.py

CSV: name,value,derived
"""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")

B, S, NS, M = 8, 32, 4, 4      # global batch, seq, super-blocks, microbatches
STAGES = (1, 2, 4)
ITEMSIZE = 4                   # reduced configs run f32 on CPU
TOL = 1e-5                     # ISSUE-5 acceptance: loss/grad parity bound

_BODY = f"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import jax, numpy as np
from dataclasses import replace
from repro.configs import get_config
from repro.dist import make_mesh
from repro.models import get_model, reduced
from repro.perf_flags import reset_flags, set_flags
from repro.launch.dryrun import collective_bytes

B, S, NS, M = {B}, {S}, {NS}, {M}
cfg = replace(reduced(get_config("qwen1.5-0.5b")), n_layers=NS)
m = get_model(cfg)
params = m.init(jax.random.PRNGKey(0))
batch = m.make_batch(jax.random.PRNGKey(1), "train", B, S)

loss_fn = lambda p: m.loss(p, batch)[0]
loss0 = float(loss_fn(params))
g0 = jax.grad(loss_fn)(params)

for P in {STAGES}:
    mesh = make_mesh((P,), ("stage",))
    set_flags(pp_stages=P, microbatches=M)
    try:
        with jax.set_mesh(mesh):
            jf = jax.jit(loss_fn)
            jg = jax.jit(jax.grad(loss_fn))
            cf = jf.lower(params).compile()
            cg = jg.lower(params).compile()
            loss1 = float(jf(params))
            g1 = jg(params)
    finally:
        reset_flags()
    for name, comp in (("fwd", cf), ("grad", cg)):
        coll = collective_bytes(comp.as_text())
        print(f"RESULT,P{{P}},{{name}}_permute_bytes,"
              f"{{int(coll['raw']['collective-permute'])}}")
        print(f"RESULT,P{{P}},{{name}}_permute_count,"
              f"{{coll['counts']['collective-permute']}}")
    gerr = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
               for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)))
    print(f"RESULT,P{{P}},loss_maxerr,{{abs(loss1 - loss0)}}")
    print(f"RESULT,P{{P}},grad_maxerr,{{gerr}}")
"""


def _measure() -> dict:
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _BODY], capture_output=True,
                       text=True, env=env, timeout=560)
    if r.returncode != 0:
        raise RuntimeError(
            f"bench_pipeline subprocess failed:\n{r.stderr[-2000:]}")
    out = {}
    for line in r.stdout.splitlines():
        if line.startswith("RESULT,"):
            _, tag, metric, value = line.split(",")
            out[(tag, metric)] = float(value)
    return out


def _analytic(P: int) -> dict:
    from repro.dist.pipeline import pipeline_permute_bytes

    # payload = the residual-stream microbatch (b, S, d_model); the bench
    # mesh has no data axis, so b = B / M.  d_model matches reduced()
    return pipeline_permute_bytes(B // M, S, 256, n_stages=P,
                                  microbatches=M, itemsize=ITEMSIZE)


def run(csv: bool = True):
    from repro.dist.pipeline import pipeline_bubble_fraction
    vals = _measure()
    rows = []

    def emit(name, value, derived=""):
        rows.append((name, value, derived))
        if csv:
            print(f"{name},{value},{derived}")

    for P in STAGES:
        model = _analytic(P)
        tag = f"P{P}"
        derived = {
            "fwd": f"{model['fwd_permutes']} hops x "
                   f"{model['payload_bytes']}B",
            "grad": f"fwd + {model['bwd_permutes']} reverse hops",
        }
        for d, key in (("fwd", "fwd_total"), ("grad", "grad_total")):
            emit(f"pipeline_{tag}_{d}_permute_bytes_hlo",
                 vals[(tag, f"{d}_permute_bytes")],
                 f"{int(vals[(tag, f'{d}_permute_count')])} permutes")
            emit(f"pipeline_{tag}_{d}_permute_bytes_analytic", model[key],
                 derived[d])
        emit(f"pipeline_{tag}_loss_maxerr", vals[(tag, "loss_maxerr")],
             f"vs single-stage baseline (tol {TOL})")
        emit(f"pipeline_{tag}_grad_maxerr", vals[(tag, "grad_maxerr")],
             f"vs single-stage baseline (tol {TOL})")
        emit(f"pipeline_{tag}_bubble_fraction",
             pipeline_bubble_fraction(P, M),
             f"(pp-1)/(pp-1+M), M={M}")
    return rows


def validate(rows) -> list[str]:
    """Acceptance (ISSUE 5): analytic permute bytes == compiled-HLO bytes
    exactly for pp in {1, 2, 4}, and pipelined loss/grads match the
    single-stage baseline within 1e-5."""
    d = {name: value for name, value, _ in rows}
    failures = []
    for P in STAGES:
        tag = f"P{P}"
        for direction in ("fwd", "grad"):
            hlo = d.get(f"pipeline_{tag}_{direction}_permute_bytes_hlo")
            ana = d.get(f"pipeline_{tag}_{direction}_permute_bytes_analytic")
            if hlo is None or ana is None:
                failures.append(
                    f"missing pipeline measurement {tag}/{direction}")
            elif hlo != ana:
                failures.append(
                    f"{tag} {direction}: HLO permute bytes {hlo} != "
                    f"analytic {ana}")
        for metric in ("loss_maxerr", "grad_maxerr"):
            err = d.get(f"pipeline_{tag}_{metric}")
            if err is None:
                failures.append(f"missing pipeline {tag} {metric}")
            elif err > TOL:
                failures.append(
                    f"{tag}: {metric} {err} exceeds {TOL} vs the "
                    f"single-stage baseline")
    multi = [P for P in STAGES if P > 1]
    if not any(d.get(f"pipeline_P{P}_fwd_permute_bytes_hlo", 0)
               for P in multi):
        failures.append("no collective-permutes found on any multi-stage "
                        "mesh — the pipeline schedule did not run")
    return failures


if __name__ == "__main__":
    rows = run()
    bad = validate(rows)
    print("PASS" if not bad else bad)
    sys.exit(1 if bad else 0)
