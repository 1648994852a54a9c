"""Pallas kernel benchmarks: oracle parity + the autotune sweep.

On this CPU container the kernels run in interpret mode, so absolute
wall-clock is NOT the kernel's merit (TPU is the target).  What IS
machine-portable here:

* allclose vs the jnp oracle at benchmark shapes (maxerr rows);
* the registry autotune sweep (DESIGN.md §13): every registered op's
  tunable space timed on its canned bench cases, reporting tuned-vs-
  default speedup.  Defaults are always in the sweep, so speedup >= 1.0
  by construction; the geomean over all cases is the gated primary (a
  same-run timing *ratio*, which survives machine changes);
* int8 paged-KV accuracy: kernel vs the quantized oracle (tight) and the
  quantized oracle vs full-precision attention (the information actually
  lost to 1-byte codes, gated loosely);
* fused sampling kernel vs the ``ref.py`` oracle under fixed keys —
  exact token match required.

CSV: name,value,derived
"""
from __future__ import annotations

import math
import sys
import tempfile
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
for p in (str(_ROOT), str(_ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.autotune import AutotuneCache, tune
from repro.kernels import registry
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.quant import kv_quantize_rows
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.sampling import sample_tokens
from repro.kernels.fused_update import sgd_momentum

TUNE_REPEATS = 3
INT8_VS_FP_TOL = 5e-2      # information lost to 1-byte codes, not a bug


def time_fn(fn, n=10, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / n * 1e6


def _paged_setup(kv_dtype=None):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    B, H, K, hd, bs, NB, P = 4, 8, 2, 64, 16, 12, 4
    q = jax.random.normal(ks[0], (B, H, hd))
    kp = jax.random.normal(ks[1], (1, NB, bs, K, hd))
    vp = jax.random.normal(ks[2], (1, NB, bs, K, hd))
    tables = jnp.arange(1, 1 + B * P, dtype=jnp.int32).reshape(B, P) % NB
    lengths = jnp.asarray([37, 32, 1, 64], jnp.int32)
    kw = {}
    if kv_dtype is not None:
        kp, kw["k_scale"] = kv_quantize_rows(kp, kv_dtype)
        vp, kw["v_scale"] = kv_quantize_rows(vp, kv_dtype)
    lanes = (1, NB, bs, K * hd)                  # the stacked pool's rows
    return (q, kp.reshape(lanes), vp.reshape(lanes), tables, lengths, 0), kw


def run(csv=True):
    rows = []
    key = jax.random.PRNGKey(0)

    # -- oracle parity + oracle wall (the TPU kernel's bar) ----------------
    B, S, H, K, hd = 1, 512, 8, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
    out = flash_attention(q, k, v, causal=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    err = float(np.abs(np.asarray(out) - np.asarray(want)).max())
    rows.append(("kernel_flash_attn_maxerr", err, "interpret vs oracle"))
    oracle = jax.jit(lambda: ref.flash_attention_ref(q, k, v, causal=True))
    rows.append(("kernel_flash_attn_oracle_us", round(time_fn(oracle), 1),
                 "jnp oracle wall (TPU kernel must beat)"))

    x = jax.random.normal(ks[0], (4096, 1024), jnp.float32)
    w = jax.random.normal(ks[1], (1024,)) * 0.1
    err = float(np.abs(np.asarray(rmsnorm(x, w))
                       - np.asarray(ref.rmsnorm_ref(x, w))).max())
    rows.append(("kernel_rmsnorm_maxerr", err, ""))
    oracle = jax.jit(lambda: ref.rmsnorm_ref(x, w))
    rows.append(("kernel_rmsnorm_oracle_us", round(time_fn(oracle), 1), ""))

    p = jax.random.normal(ks[0], (1 << 20,))
    g = jax.random.normal(ks[1], (1 << 20,))
    m = jnp.zeros((1 << 20,))
    new_p, new_m = sgd_momentum(p, g, m, lr=0.1, mu=0.9, weight_decay=1e-4)
    wp, wm = ref.sgd_momentum_ref(p, g, m, lr=0.1, mu=0.9, weight_decay=1e-4)
    err = float(np.abs(np.asarray(new_p) - np.asarray(wp)).max())
    rows.append(("kernel_fused_update_maxerr", err, "1M params"))
    oracle = jax.jit(lambda: ref.sgd_momentum_ref(p, g, m, lr=0.1, mu=0.9,
                                                  weight_decay=1e-4))
    rows.append(("kernel_fused_update_oracle_us", round(time_fn(oracle), 1),
                 ""))

    # paged attention: fp oracle parity at GQA + block-boundary lengths
    args, _ = _paged_setup()
    err = float(jnp.abs(paged_attention(*args)
                        - ref.paged_attention_ref(*args)).max())
    rows.append(("kernel_paged_attn_maxerr", err,
                 "interpret vs oracle, GQA + block boundary"))

    # -- int8 paged KV-cache accuracy (DESIGN.md §13) ----------------------
    qargs, qkw = _paged_setup(kv_dtype=jnp.int8)
    got = paged_attention(*qargs, **qkw)
    qref = ref.paged_attention_ref(*qargs, **qkw)
    rows.append(("kernel_paged_int8_vs_qref_maxerr",
                 float(jnp.abs(got - qref).max()),
                 "kernel vs quantized oracle (same math, tight)"))
    fpref = ref.paged_attention_ref(*args)
    rows.append(("kernel_paged_int8_vs_fp_err",
                 float(jnp.abs(got - fpref).max()),
                 f"quantization loss, tol {INT8_VS_FP_TOL}"))

    # -- fused sampling vs ref oracle (exact token parity) ------------------
    mism = 0
    n_toks = 0
    for i, kwargs in enumerate([
            {"temperature": 0.0},
            {"temperature": 1.0, "top_k": 5},
            {"temperature": 0.7, "top_p": 0.8},
            {"temperature": 0.8, "top_k": 50, "top_p": 0.9}]):
        kk = jax.random.split(jax.random.PRNGKey(20 + i), 2)
        logits = jax.random.normal(kk[0], (8, 512)) * 3.0
        u = jax.random.uniform(kk[1], (8,))
        a = np.asarray(sample_tokens(logits, u, **kwargs))
        b = np.asarray(ref.sample_ref(logits, u, **kwargs))
        mism += int((a != b).sum())
        n_toks += a.size
    rows.append(("kernel_sampling_token_mismatches", mism,
                 f"{n_toks} draws: greedy/top-k/top-p/both vs ref oracle"))
    logits = jax.random.normal(jax.random.PRNGKey(30), (8, 2048)) * 3.0
    u = jax.random.uniform(jax.random.PRNGKey(31), (8,))
    oracle = jax.jit(lambda: ref.sample_ref(logits, u, temperature=0.8,
                                            top_k=50, top_p=0.9))
    rows.append(("kernel_sampling_oracle_us", round(time_fn(oracle), 1),
                 "host-style filtered sampling, B8 V2048"))

    # -- the autotune sweep (tuned vs default, every registered op) ---------
    cache = AutotuneCache(Path(tempfile.mkdtemp()) / "autotune.json")
    speedups = []
    for op in registry.ops():
        spec = registry.get(op)
        for label, make in spec.bench_cases:
            a, kw = make()
            rep = tune(op, a, kw, cache=cache, repeats=TUNE_REPEATS,
                       save=False)
            win = " ".join(f"{k}={v}" for k, v in sorted(rep["params"].items()))
            rows.append((f"kernel_tune_{op}_{label}_speedup",
                         round(rep["speedup"], 3),
                         f"winner {win}: {rep['tuned_us']:.0f}us vs default "
                         f"{rep['default_us']:.0f}us"))
            speedups.append((op, label, rep["speedup"],
                             rep["params"] != spec.defaults))
    geo = math.exp(sum(math.log(s) for _, _, s, _ in speedups)
                   / len(speedups))
    rows.append(("kernels_tuned_speedup_geomean", round(geo, 3),
                 f"{len(speedups)} (op, shape) cases; defaults always in "
                 f"the sweep so each case >= 1.0"))

    if csv:
        print("name,value,derived")
        for r in rows:
            print(",".join(str(x) for x in r))
    return rows


def validate(rows):
    fails = []
    d = {name: val for name, val, _ in rows}
    for name, val in d.items():
        if name.endswith("maxerr") and val > 1e-4:
            fails.append(f"{name}: {val}")
    if d.get("kernel_paged_int8_vs_fp_err", 1.0) > INT8_VS_FP_TOL:
        fails.append(f"int8 quantization loss "
                     f"{d.get('kernel_paged_int8_vs_fp_err')} > "
                     f"{INT8_VS_FP_TOL}")
    if d.get("kernel_sampling_token_mismatches", 1) != 0:
        fails.append(f"sampling kernel disagrees with ref oracle on "
                     f"{d.get('kernel_sampling_token_mismatches')} draws")
    tuned = {n: v for n, v in d.items()
             if n.startswith("kernel_tune_") and n.endswith("_speedup")}
    if not tuned:
        fails.append("no autotune sweep rows")
    for name, s in tuned.items():
        if s < 0.99:    # >= 1.0 by construction; 1% float/timing guard
            fails.append(f"{name}: tuned slower than default ({s})")
    if tuned and max(tuned.values()) <= 1.05:
        fails.append("no op shows a strict tuned-vs-default win "
                     f"(max speedup {max(tuned.values())})")
    return fails


if __name__ == "__main__":
    rows = run()
    print("VALIDATION:", validate(rows) or "PASS")
    sys.exit(1 if validate(rows) else 0)
