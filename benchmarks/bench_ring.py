"""Ring attention on a sequence-sharded mesh (DESIGN.md §8): per-device
peak attention activation bytes vs the number of sequence shards, and the
analytic collective-permute byte model cross-validated against the
compiled HLO — the same HLO-vs-model discipline ``bench_dist.py``
established for the all-reduce schedules.

For each shard count P in {1, 2, 4, 8} (one mesh axis, "model"):

* lower + compile ``ring_attention`` forward and grad on a fixed
  (B=1, S=4096, H=8, K=4, hd=64) f32 problem;
* read ``memory_analysis().temp_size_in_bytes`` — the per-device peak of
  the attention activations (the jitted function *is* the attention call,
  so temps are scores/probs/carry state only).  The claim under test: it
  shrinks at least ~linearly in P (the score block alone shrinks
  quadratically: (S/P)² per step instead of S²);
* parse collective-permute bytes out of the compiled HLO and require them
  to equal ``ring_permute_bytes`` *exactly* — forward
  ``max(contributing_steps)·2·chunk``, grad adds the reverse ring's
  ``(P-1)·2·chunk + P·2·chunk_f32`` (dk/dv are f32 accumulators);
* repeat at P=8 with a sliding window that masks all but one ring hop,
  checking the windowed early-stop byte model.

Multi-device lowering needs --xla_force_host_platform_device_count before
jax initializes, so measurement runs in a subprocess (CSV rows out).

Usage:  PYTHONPATH=src python benchmarks/bench_ring.py

CSV: name,value,derived
"""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")

B, S, H, K, HD = 1, 4096, 8, 4, 64
SHARDS = (1, 2, 4, 8)
WINDOW = 512          # at P=8 (chunk 512): ring steps 0..1 contribute
ITEMSIZE = 4          # f32 on the CPU bench

_BODY = f"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import jax, jax.numpy as jnp
from repro.dist import make_mesh
from repro.dist.ring import ring_attention
from repro.launch.dryrun import collective_bytes

B, S, H, K, HD = {B}, {S}, {H}, {K}, {HD}
q = jnp.zeros((B, S, H, HD), jnp.float32)
k = jnp.zeros((B, S, K, HD), jnp.float32)
v = jnp.zeros((B, S, K, HD), jnp.float32)

def measure(P, window):
    mesh = make_mesh((P,), ("model",))
    def attn(q, k, v):
        return ring_attention(q, k, v, causal=True, window=window)
    def loss(q, k, v):
        return ring_attention(q, k, v, causal=True,
                              window=window).astype(jnp.float32).sum()
    with jax.set_mesh(mesh):
        cf = jax.jit(attn).lower(q, k, v).compile()
        cg = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, k, v).compile()
    tag = f"P{{P}}" + ("" if window is None else f"_w{{window}}")
    for name, comp in (("fwd", cf), ("grad", cg)):
        coll = collective_bytes(comp.as_text())
        mem = comp.memory_analysis()
        print(f"RESULT,{{tag}},{{name}}_permute_bytes,"
              f"{{int(coll['raw']['collective-permute'])}}")
        print(f"RESULT,{{tag}},{{name}}_permute_count,"
              f"{{coll['counts']['collective-permute']}}")
        print(f"RESULT,{{tag}},{{name}}_peak_temp_bytes,"
              f"{{mem.temp_size_in_bytes}}")

for P in {SHARDS}:
    measure(P, None)
measure(8, {WINDOW})
"""


def _measure() -> dict:
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _BODY], capture_output=True,
                       text=True, env=env, timeout=560)
    if r.returncode != 0:
        raise RuntimeError(f"bench_ring subprocess failed:\n{r.stderr[-2000:]}")
    out = {}
    for line in r.stdout.splitlines():
        if line.startswith("RESULT,"):
            _, tag, metric, value = line.split(",")
            out[(tag, metric)] = float(value)
    return out


def _analytic(P: int, window=None) -> dict:
    from repro.dist.ring import ring_permute_bytes
    return ring_permute_bytes(B, S, K, HD, P, itemsize=ITEMSIZE,
                              causal=True, window=window)


def run(csv: bool = True):
    vals = _measure()
    rows = []

    def emit(name, value, derived=""):
        rows.append((name, value, derived))
        if csv:
            print(f"{name},{value},{derived}")

    for P in SHARDS:
        model = _analytic(P)
        tag = f"P{P}"
        derived = {
            "fwd": f"{model['fwd_rotations']} rot x {model['per_step_fwd']}B",
            "grad": f"fwd + {model['bwd_rotations']} bwd rot",
        }
        for d, key in (("fwd", "fwd_total"), ("grad", "grad_total")):
            emit(f"ring_{tag}_{d}_permute_bytes_hlo",
                 vals[(tag, f"{d}_permute_bytes")],
                 f"{int(vals[(tag, f'{d}_permute_count')])} permutes")
            emit(f"ring_{tag}_{d}_permute_bytes_analytic", model[key],
                 derived[d])
        emit(f"ring_{tag}_fwd_peak_temp_bytes",
             vals[(tag, "fwd_peak_temp_bytes")],
             f"S/P={S // P}")
    # windowed early-stop model at P=8
    model = _analytic(8, window=WINDOW)
    tag = f"P8_w{WINDOW}"
    emit(f"ring_{tag}_fwd_permute_bytes_hlo",
         vals[(tag, "fwd_permute_bytes")])
    emit(f"ring_{tag}_fwd_permute_bytes_analytic", model["fwd_total"],
         f"{model['fwd_rotations']} of 7 rotations (window early-stop)")
    emit(f"ring_{tag}_grad_permute_bytes_hlo",
         vals[(tag, "grad_permute_bytes")])
    emit(f"ring_{tag}_grad_permute_bytes_analytic", model["grad_total"])
    return rows


def validate(rows) -> list[str]:
    """Acceptance (ISSUE 3): analytic permute bytes == compiled-HLO bytes
    exactly, and per-device peak attention bytes shrink ~linearly in P."""
    d = {name: value for name, value, _ in rows}
    failures = []
    tags = [f"P{P}" for P in SHARDS] + [f"P8_w{WINDOW}"]
    for tag in tags:
        for direction in ("fwd", "grad"):
            hlo = d.get(f"ring_{tag}_{direction}_permute_bytes_hlo")
            ana = d.get(f"ring_{tag}_{direction}_permute_bytes_analytic")
            if hlo is None or ana is None:
                failures.append(f"missing ring measurement {tag}/{direction}")
            elif hlo != ana:
                failures.append(
                    f"{tag} {direction}: HLO permute bytes {hlo} != "
                    f"analytic {ana}")
    multi = [P for P in SHARDS if P > 1]
    if not any(d.get(f"ring_P{P}_fwd_permute_bytes_hlo", 0) for P in multi):
        failures.append("no collective-permutes found on any multi-shard "
                        "mesh — the ring schedule did not run")
    peaks = {P: d.get(f"ring_P{P}_fwd_peak_temp_bytes", 0) for P in SHARDS}
    if not all(peaks.values()):
        failures.append(f"missing/zero peak temp bytes: {peaks}")
    else:
        for prev, P in zip(SHARDS, SHARDS[1:]):
            if peaks[P] > peaks[prev] / 1.5:
                failures.append(
                    f"peak attention bytes did not shrink ~linearly: "
                    f"P={prev}: {peaks[prev]:.0f} -> P={P}: {peaks[P]:.0f} "
                    f"(ratio {peaks[prev] / peaks[P]:.2f} < 1.5)")
    return failures


if __name__ == "__main__":
    rows = run()
    bad = validate(rows)
    print("PASS" if not bad else bad)
    sys.exit(1 if bad else 0)
