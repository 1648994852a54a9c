"""Serving under mixed-length traffic: static padded batches vs paged
continuous batching (ISSUE 4, DESIGN.md §9).

Workload: requests with prompt lengths drawn from {32..512} (skewed
short, like real traffic) and uneven generation budgets.  The static
engine processes them in arrival-order lockstep batches — every batch
pads to the global max prompt length, allocates dense ``(B, max_len)``
caches, and decodes until its SLOWEST request finishes.  The paged
engine streams the same requests through ``max_batch`` decode lanes over
a block pool: finished lanes are refilled immediately, prompts prefill
in chunks, cache blocks are recycled.

Reported (CSV name,value,derived):

* greedy-token parity between the engines (they must implement the same
  math — continuous batching is a *scheduling* change);
* decode tokens/s: useful tokens (each request's own budget) over decode
  wall time, per engine — the headline claim: paged > static;
* peak KV-cache bytes: dense ``B x max_len`` model vs the allocator's
  block high-water mark — the claim: >= 4x smaller paged;
* paged-attention kernel vs oracle max |err| (GQA + block-boundary
  lengths), interpret mode.

Usage:  PYTHONPATH=src python benchmarks/bench_serving.py
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
for p in (str(_ROOT), str(_ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np

N_REQUESTS = 24
MAX_BATCH = 8
BLOCK_SIZE = 16
PREFILL_CHUNK = 128
PROMPT_LENS = [32, 48, 64, 96, 128, 192, 256, 384, 512]
# chat-like traffic: heavy short mass, thin long tail (the regime where
# dense max_len padding wastes the most cache)
PROMPT_P = [0.30, 0.22, 0.16, 0.12, 0.08, 0.05, 0.04, 0.02, 0.01]
BUDGETS = [4, 8, 16, 32, 48]
KERNEL_TOL = 5e-3
# int8 greedy-parity sub-workload (short-skewed, like the main one but
# sized so the full fp-vs-int8 token comparison runs in seconds).  The
# rng seed is part of the benchmark definition: greedy decoding is
# deterministic, so parity verified once holds run to run.
INT8_N = 8
INT8_LENS = [16, 24, 32]
INT8_BUDGETS = [4, 6, 8]
INT8_SEED = 2
INT8_RATIO_FLOOR = 1.8
# overload section (ISSUE 9 / DESIGN.md §14): arrival rate > capacity on
# an undersized pool, optimistic admission + preemption/swap, mixed
# priorities and a slice of unmeetable deadlines.  Arrivals are
# step-driven (2 per engine step), so the pressure pattern — and hence
# the shed/preempt structure — does not depend on wall clock.
OV_N = 16
OV_ARRIVALS_PER_STEP = 2
OV_MAX_QUEUE = 4
OV_SEED = 5


def _workload(vocab: int, seed: int = 2):
    rng = np.random.RandomState(seed)
    lens = rng.choice(PROMPT_LENS, N_REQUESTS, p=PROMPT_P)
    budgets = [int(b) for b in rng.choice(BUDGETS, N_REQUESTS)]
    prompts = [list(rng.randint(1, vocab, int(L))) for L in lens]
    return prompts, budgets


def _short_workload(vocab: int, seed: int = INT8_SEED):
    rng = np.random.RandomState(seed)
    lens = rng.choice(INT8_LENS, INT8_N)
    budgets = [int(b) for b in rng.choice(INT8_BUDGETS, INT8_N)]
    prompts = [list(rng.randint(1, vocab, int(L))) for L in lens]
    return prompts, budgets


def _kernel_parity():
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.paged_attention import paged_attention

    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    B, H, K, hd, bs, NB, P = 4, 8, 2, 64, 16, 12, 4
    q = jax.random.normal(ks[0], (B, H, hd))
    kp = jax.random.normal(ks[1], (1, NB, bs, K * hd))
    vp = jax.random.normal(ks[2], (1, NB, bs, K * hd))
    tables = jnp.arange(1, 1 + B * P, dtype=jnp.int32).reshape(B, P) % NB
    # mid-block, exact boundary, one token, full table
    lengths = jnp.asarray([37, 32, 1, 64], jnp.int32)
    out = paged_attention(q, kp, vp, tables, lengths, 0)
    want = ref.paged_attention_ref(q, kp, vp, tables, lengths, 0)
    return float(jnp.abs(out - want).max())


def run(csv: bool = True, kv_dtype: str = "int8"):
    import jax
    from repro.configs import get_config
    from repro.core.memplan import kv_cache_bytes_dense
    from repro.models import get_model, reduced
    from repro.serve import PagedServeEngine, ServeEngine

    cfg = reduced(get_config("qwen1.5-0.5b"))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts, budgets = _workload(cfg.vocab)
    max_len = max(PROMPT_LENS) + max(BUDGETS) + 8
    # decode-produced tokens only: each request's FIRST token comes from
    # prefill logits on both engines, so it belongs to neither decode timer
    useful = sum(b - 1 for b in budgets)

    rows = []

    def emit(name, value, derived=""):
        rows.append((name, value, derived))
        if csv:
            print(f"{name},{value},{derived}")

    # -- static lockstep batches (arrival order) ---------------------------
    eng = ServeEngine(cfg, params, max_len=max_len)
    static_out = []
    static_decode_s = static_prefill_s = compile_s = 0.0
    for i in range(0, N_REQUESTS, MAX_BATCH):
        bp = prompts[i:i + MAX_BATCH]
        bb = budgets[i:i + MAX_BATCH]
        toks, st = eng.generate(bp, max_new_tokens=max(bb),
                                pad_prompts_to=max(PROMPT_LENS),
                                warmup=(i == 0))
        compile_s += st.compile_s
        static_decode_s += st.decode_s
        static_prefill_s += st.prefill_s
        static_out += [list(map(int, toks[j, :bb[j]])) for j in range(len(bp))]
    static_tok_s = useful / static_decode_s
    emit("serving_static_decode_tok_per_s", round(static_tok_s, 1),
         f"{useful} useful decode tokens / {static_decode_s:.3f}s "
         f"(compile {compile_s:.1f}s separate)")

    # -- paged continuous batching ----------------------------------------
    peng = PagedServeEngine(cfg, params, block_size=BLOCK_SIZE,
                            max_batch=MAX_BATCH, max_len=max_len,
                            prefill_chunk=PREFILL_CHUNK)
    t0 = time.time()
    paged_out, pst = peng.generate(prompts, max_new_tokens=budgets)
    wall = time.time() - t0
    paged_tok_s = pst.tokens_out / pst.decode_s
    emit("serving_paged_decode_tok_per_s", round(paged_tok_s, 1),
         f"{pst.tokens_out} decode tokens / {pst.decode_s:.3f}s in "
         f"{pst.steps} steps (compile {pst.compile_s:.1f}s separate)")
    emit("serving_paged_wall_s", round(wall - pst.compile_s, 3),
         f"prefill {pst.prefill_s:.3f}s")
    emit("serving_speedup", round(paged_tok_s / static_tok_s, 2),
         "paged/static decode tok/s")

    # -- per-request latency (informational, never gated: wall-clock
    #    percentiles swing with machine load like every timing here) -------
    emit("serving_ttft_p50_ms", round(pst.ttft_p50 * 1e3, 2),
         "enqueue -> first token (paged engine)")
    emit("serving_ttft_p99_ms", round(pst.ttft_p99 * 1e3, 2), "")
    emit("serving_tpot_p50_ms", round(pst.tpot_p50 * 1e3, 3),
         "per-token decode time after the first")
    emit("serving_tpot_p99_ms", round(pst.tpot_p99 * 1e3, 3), "")
    emit("serving_queue_wait_p50_ms", round(pst.queue_wait_p50 * 1e3, 2),
         "enqueue -> admission to a decode lane")
    emit("serving_queue_wait_p99_ms", round(pst.queue_wait_p99 * 1e3, 2), "")

    # -- parity ------------------------------------------------------------
    mismatches = sum(a != b for a, b in zip(static_out, paged_out))
    emit("serving_token_mismatches", mismatches,
         f"{N_REQUESTS} mixed-length greedy requests")

    # -- cache bytes -------------------------------------------------------
    dense = kv_cache_bytes_dense(cfg, MAX_BATCH, max_len)
    emit("serving_dense_cache_bytes", dense,
         f"{MAX_BATCH} x max_len={max_len} padded")
    emit("serving_paged_peak_cache_bytes", pst.peak_cache_bytes,
         f"{pst.peak_cache_blocks} blocks (block_size {BLOCK_SIZE})")
    emit("serving_cache_ratio",
         round(dense / max(pst.peak_cache_bytes, 1), 2),
         "dense / paged peak")

    # -- int8 paged KV-cache (DESIGN.md §13) -------------------------------
    # same full workload through a quantized-cache engine: block schedule
    # depends only on lengths/budgets, so fp and int8 peaks count the SAME
    # blocks — the byte ratio is purely bytes-per-block (codes + scales
    # vs native rows) and is allocator-deterministic
    qeng = PagedServeEngine(cfg, params, block_size=BLOCK_SIZE,
                            max_batch=MAX_BATCH, max_len=max_len,
                            prefill_chunk=PREFILL_CHUNK, kv_dtype=kv_dtype)
    q_out, qst = qeng.generate(prompts, max_new_tokens=budgets)
    emit("serving_int8_decode_tok_per_s",
         round(qst.tokens_out / qst.decode_s, 1),
         f"{kv_dtype}; informational: interpret-mode wall, not the TPU "
         f"story")
    emit("serving_int8_peak_cache_bytes", qst.peak_cache_bytes,
         f"{qst.peak_cache_blocks} blocks incl. per-row f32 scales "
         f"({kv_dtype})")
    emit("serving_int8_vs_fp_cache_ratio",
         round(pst.peak_cache_bytes / max(qst.peak_cache_bytes, 1), 2),
         f"fp paged peak / int8 paged peak (floor {INT8_RATIO_FLOOR})")

    # greedy-token parity fp vs int8 on the short-skewed sub-workload:
    # 1-byte codes perturb logits by ~1e-2, so near-tie argmaxes can flip
    # on long decodes; short generations with healthy top-1 margins must
    # agree EXACTLY, and greedy determinism makes this stable run to run
    sp, sb = _short_workload(cfg.vocab)
    s_len = max(INT8_LENS) + max(INT8_BUDGETS) + 8
    parity_out = {}
    for kd in (None, kv_dtype):
        e = PagedServeEngine(cfg, params, block_size=BLOCK_SIZE,
                             max_batch=MAX_BATCH, max_len=s_len,
                             prefill_chunk=32, kv_dtype=kd)
        parity_out[kd], _ = e.generate(sp, max_new_tokens=sb, warmup=False)
    q_mism = sum(int(a != b)
                 for ta, tb in zip(parity_out[None], parity_out[kv_dtype])
                 for a, b in zip(ta, tb))
    emit("serving_int8_token_mismatches", q_mism,
         f"{sum(sb)} greedy tokens, {INT8_N} short-skewed requests")

    # -- overload: traffic > capacity (ISSUE 9, DESIGN.md §14) -------------
    # undersized pool + bounded queue + tight deadlines: the engine must
    # degrade (preempt / shed / time out), never crash, and leave every
    # request in a typed terminal status
    from repro.serve import ServeStats, Status

    rng = np.random.RandomState(OV_SEED)
    ov_lens = rng.randint(16, 65, OV_N)
    ov_budgets = [int(b) for b in rng.randint(8, 25, OV_N)]
    ov_prios = [int(p) for p in rng.randint(0, 3, OV_N)]
    # every 5th request gets a deadline it cannot meet (1ms): exercises
    # the timeout sweep + deadline-miss accounting
    ov_deadlines = [1.0 if i % 5 == 3 else None for i in range(OV_N)]
    ov_prompts = [list(rng.randint(1, cfg.vocab, int(L))) for L in ov_lens]
    oeng = PagedServeEngine(
        cfg, params, block_size=16, max_batch=4, max_len=96,
        prefill_chunk=32, num_blocks=13,        # 12 usable << 4 lanes x 6
        admission="optimistic", swap_blocks=18,
        victim_policy="lowest_priority",
        max_queue=OV_MAX_QUEUE, shed_policy="reject_newest")
    ost = ServeStats()
    ost.compile_s = oeng.warmup()
    tickets, crashes, i = [], 0, 0
    try:
        while i < OV_N or oeng.busy:
            for _ in range(OV_ARRIVALS_PER_STEP):
                if i < OV_N:
                    tickets.append(oeng.add_request(
                        ov_prompts[i], ov_budgets[i],
                        priority=ov_prios[i],
                        deadline_ms=ov_deadlines[i]))
                    i += 1
            oeng.step(ost)
        oeng.run(ost)          # drained: fills the lifecycle counters
    except Exception as e:     # the gate: overload must never raise
        crashes = 1
        print(f"# overload section crashed: {type(e).__name__}: {e}",
              file=sys.stderr)
    accepted = sum(t.accepted for t in tickets)
    terminal = sum(1 for t in tickets
                   if t.rid in oeng.results
                   and isinstance(oeng.results[t.rid].status, Status))
    misses = sorted(r.deadline_miss_s for r in oeng.results.values()
                    if r.deadline_miss_s is not None)
    emit("serving_overload_crashes", crashes,
         f"{OV_N} requests at {OV_ARRIVALS_PER_STEP}/step, queue "
         f"{OV_MAX_QUEUE}, 12-block pool")
    emit("serving_overload_terminal_coverage",
         round(terminal / OV_N, 3),
         "fraction of requests with a typed terminal status (gate: 1.0)")
    emit("serving_overload_preempt_rate",
         round(ost.preempted / max(accepted, 1), 3),
         f"{ost.preempted} preemptions / {accepted} accepted "
         f"({ost.restored} restored, swap peak {ost.swap_peak_blocks} "
         f"blocks)")
    emit("serving_overload_shed_rate", round(ost.shed / OV_N, 3),
         f"{ost.shed} shed of {OV_N} submitted (bounded queue)")
    emit("serving_overload_timeouts", ost.timeouts,
         f"{sum(d is not None for d in ov_deadlines)} requests carried "
         f"unmeetable 1ms deadlines")
    emit("serving_overload_deadline_miss_p99_ms",
         round(float(np.percentile(misses, 99)) * 1e3, 2) if misses else 0,
         "informational: wall-clock dependent")
    emit("serving_overload_goodput_tok_per_s",
         round(ost.goodput_tok_per_s, 1),
         f"{ost.goodput_tokens} decode tokens of OK requests / "
         f"{ost.decode_s:.3f}s decode")

    # -- kernel ------------------------------------------------------------
    emit("serving_paged_kernel_max_err", _kernel_parity(),
         "pallas interpret vs oracle, GQA + block boundary")
    return rows


def validate(rows) -> list[str]:
    """Acceptance (ISSUE 4 + 9): identical greedy tokens, paged beats
    static decode tok/s, >= 4x smaller peak cache, kernel matches the
    oracle; the overload run crashes zero times, leaves every request in
    a typed terminal status, and actually exercises preemption+shedding."""
    d = {name: value for name, value, _ in rows}
    failures = []
    if d.get("serving_overload_crashes", 1) != 0:
        failures.append("overload section raised instead of degrading")
    if d.get("serving_overload_terminal_coverage", 0) != 1.0:
        failures.append(
            f"overload terminal coverage "
            f"{d.get('serving_overload_terminal_coverage')} != 1.0")
    if not d.get("serving_overload_preempt_rate", 0) > 0:
        failures.append("overload run never preempted (pool not stressed)")
    if not d.get("serving_overload_shed_rate", 0) > 0:
        failures.append("overload run never shed (queue bound not hit)")
    if not d.get("serving_overload_timeouts", 0) > 0:
        failures.append("overload run never timed out a doomed deadline")
    if d.get("serving_token_mismatches", 1) != 0:
        failures.append(
            f"static and paged engines disagree on "
            f"{d.get('serving_token_mismatches')} requests")
    if not d.get("serving_paged_decode_tok_per_s", 0) > \
            d.get("serving_static_decode_tok_per_s", float("inf")):
        failures.append(
            f"paged decode tok/s {d.get('serving_paged_decode_tok_per_s')} "
            f"<= static {d.get('serving_static_decode_tok_per_s')}")
    ratio = d.get("serving_cache_ratio", 0)
    if ratio < 4.0:
        failures.append(f"dense/paged peak cache ratio {ratio} < 4.0")
    qratio = d.get("serving_int8_vs_fp_cache_ratio", 0)
    if qratio < INT8_RATIO_FLOOR:
        failures.append(f"int8 cache ratio {qratio} < {INT8_RATIO_FLOOR}")
    if d.get("serving_int8_token_mismatches", 1) != 0:
        failures.append(
            f"int8 engine disagrees with fp greedy tokens on "
            f"{d.get('serving_int8_token_mismatches')} draws "
            f"(short-skewed parity workload)")
    err = d.get("serving_paged_kernel_max_err", 1.0)
    if err > KERNEL_TOL:
        failures.append(f"paged kernel max err {err} > {KERNEL_TOL}")
    return failures


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--kv-dtype", default="int8",
                    choices=["int8", "fp8_e4m3", "fp8_e5m2"],
                    help="storage dtype for the quantized-cache section "
                         "(the gates are calibrated for int8)")
    rows = run(kv_dtype=ap.parse_args().kv_dtype)
    bad = validate(rows)
    print("PASS" if not bad else bad)
    sys.exit(1 if bad else 0)
