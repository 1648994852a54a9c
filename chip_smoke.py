#!/usr/bin/env python3
"""Chip smoke test: qwen1.5-0.5b serving and training on a TPU through the
repo's own entry points, at the model's full published width (24 layers,
d_model 1024, 16 heads, vocab 151936, bf16) with random weights drawn from
``--seed``.

    python3 chip_smoke.py               # serve + train on one chip
    python3 chip_smoke.py --four-chips  # data-parallel training only:
                                        # 4 chips against 1, same batches

The serve phase builds a ``PagedServeEngine`` as ``repro.launch.serve
--paged`` does, runs mixed-length greedy requests and a small sampled set
(the fused top-k/top-p sampling kernel), and checks the compiled Pallas
paged-attention kernel against its oracle on the engine's own KV pool.
The train phase runs a few ``Trainer`` steps as ``repro.launch.train``
does.  ``--four-chips`` trains with bucketed overlap sync on the
``(pod=1, data=4, model=1)`` mesh of ``repro.launch.train --mesh dist`` and
compares each step's loss with the same steps on one chip.

Earlier lines are one JSON object per phase: the device, ``compile_s``
(wall time of the warm-up request or the first train step, compilation
included), ``peak_bytes_in_use`` and the phase's checks.  No rate is
reported.  The last line, ``{"ok": true, "device": {...}}``, is printed
only when every check passed on a TPU; otherwise the script exits nonzero
without it.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen1.5-0.5b"

# serve phase: launch/serve.py --paged --mixed at these settings
REQUESTS = 12
PROMPT_LENS = (64, 512)                 # drawn uniformly, inclusive
NEW_TOKENS = 32
MAX_BATCH = 8
BLOCK_SIZE = 16
PREFILL_CHUNK = 64
SAMPLED_REQUESTS = 4
SAMPLING = dict(temperature=0.8, top_k=50, top_p=0.9)
# The kernel and the oracle both accumulate in f32 and round the output
# once to bf16, whose spacing is at most 2**-7 of the magnitude: the
# bound allows two such steps at the largest output (taken as >= 1).
PAGED_REL_ERR = 2 * 2.0 ** -7

# train phase: launch/train.py --steps 3 --batch 4 --seq 512
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 512, 3

# --four-chips: global batch 8 (2 rows per chip); the two runs reduce in
# different orders in bf16 (8-bit mantissa, 2**-8 relative rounding),
# compounded over the steps
DP_CHIPS, DP_BATCH, DP_STEPS = 4, 8, 3
DP_LOSS_RTOL = 1e-2


def check(ok: bool, what: str) -> None:
    """Fail the run: exit nonzero, with no result line."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def emit(**record) -> None:
    print(json.dumps(record), flush=True)


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def lowers_to_kernel(f, *args) -> bool:
    """Whether ``f`` lowers to a compiled Mosaic kernel call (not to the
    oracle or to interpret mode, which lower to plain XLA ops)."""
    import jax
    return "tpu_custom_call" in jax.jit(f).lower(*args).as_text()


# ---------------------------------------------------------------------------
# serve


def serve_phase(cfg, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    from repro.kernels.ops import paged_attention
    from repro.kernels.sampling import sample_tokens
    from repro.models import get_model
    from repro.serve import PagedServeEngine
    from repro.serve.engine import Status

    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    rng = np.random.RandomState(seed)
    lens = rng.randint(PROMPT_LENS[0], PROMPT_LENS[1] + 1, REQUESTS)
    prompts = [list(rng.randint(1, cfg.vocab, n)) for n in lens]
    eng = PagedServeEngine(cfg, params, block_size=BLOCK_SIZE,
                           max_batch=MAX_BATCH,
                           max_len=PROMPT_LENS[1] + NEW_TOKENS + 8,
                           prefill_chunk=PREFILL_CHUNK)

    def run(prompts, **kw):
        before = set(eng.results)
        outs, stats = eng.generate(prompts, max_new_tokens=NEW_TOKENS,
                                   seed=seed, **kw)
        status = [eng.results[r].status for r in set(eng.results) - before]
        check(len(status) == len(prompts)
              and all(s is Status.OK for s in status),
              f"requests did not all end OK: {status}")
        for o in outs:
            check(len(o) == NEW_TOKENS
                  and all(0 <= int(t) < cfg.vocab for t in o),
                  f"a request got {len(o)} tokens, not {NEW_TOKENS} "
                  f"in-vocab ones: {o}")
        return stats

    greedy = run(prompts)
    run(prompts[:SAMPLED_REQUESTS], warmup=False, **SAMPLING)

    # the decode step and the sampler really hold the compiled kernels
    B, P = MAX_BATCH, eng.max_pages
    step_batch = {"tokens": jnp.zeros((B, 1), jnp.int32),
                  "block_tables": jnp.zeros((B, P), jnp.int32),
                  "pos": jnp.zeros((B,), jnp.int32),
                  "active": jnp.ones((B,), bool)}
    check(lowers_to_kernel(model.decode_paged, params, eng.cache,
                           step_batch),
          "the paged decode step does not call the Pallas kernel")
    check(lowers_to_kernel(
        lambda l, u: sample_tokens(l, u, **SAMPLING),
        jnp.zeros((B, cfg.vocab), jnp.float32), jnp.zeros((B,), jnp.float32)),
        "the sampler does not call the Pallas kernel")

    # the compiled paged kernel against the oracle, on the last layer of
    # the engine's stacked pool (K/V the requests wrote), read as it lies,
    # with every block in a table
    pool = eng.cache["layers"]["p0"]
    k_pages, v_pages = pool["k"], pool["v"]      # (L, NB, bs, K*hd)
    layer = jnp.int32(k_pages.shape[0] - 1)
    nb = k_pages.shape[1]
    tables = jnp.asarray(rng.permutation(np.arange(1, nb))[:B * P]
                         .reshape(B, P), jnp.int32)
    lengths = jnp.asarray(rng.randint(1, P * BLOCK_SIZE + 1, B), jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (B, cfg.n_heads, cfg.hd), k_pages.dtype)
    out = paged_attention(q, k_pages, v_pages, tables, lengths, layer)
    want = jax.jit(ref.paged_attention_ref)(q, k_pages, v_pages, tables,
                                            lengths, layer)
    want = want.astype(jnp.float32)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)))
    bound = PAGED_REL_ERR * max(1.0, float(jnp.max(jnp.abs(want))))
    check(err <= bound,
          f"paged kernel vs oracle max abs error {err} > {bound}")
    return dict(phase="serve", compile_s=greedy.compile_s,
                requests=REQUESTS, prompt_lens=[int(n) for n in lens],
                new_tokens=NEW_TOKENS, sampled_requests=SAMPLED_REQUESTS,
                sampling=SAMPLING,
                kv_pool_shape=list(k_pages.shape),
                paged_max_abs_err=err,
                paged_max_abs_err_bound=bound,
                peak_cache_blocks=greedy.peak_cache_blocks)


# ---------------------------------------------------------------------------
# train


def make_trainer(cfg, batch: int, seq: int, steps: int, seed: int, *,
                 overlap: bool = False):
    """A ``Trainer`` and its data as ``repro.launch.train`` builds them,
    logging every step."""
    import jax
    from repro.data import PrefetchIterator, SyntheticLM
    from repro.train import TrainConfig, Trainer
    tcfg = TrainConfig(lr=1e-2, total_steps=steps,
                       warmup_steps=max(steps // 10, 1), log_every=1,
                       grad_clip=5.0, overlap=overlap)
    data = PrefetchIterator(
        SyntheticLM(cfg.vocab, seq, batch, seed=seed, n_batches=steps,
                    process_index=jax.process_index(),
                    process_count=jax.process_count()),
        depth=4)
    return Trainer(cfg, tcfg), data


def losses(trainer) -> list[float]:
    out = [h["loss"] for h in trainer.history]
    check(len(out) > 0 and all(math.isfinite(x) for x in out),
          f"train losses not all finite: {out}")
    return out


def train_phase(cfg, seed: int) -> dict:
    tr, data = make_trainer(cfg, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, seed)
    tr.fit(data, seed=seed)
    return dict(phase="train", compile_s=tr.history[0]["wall_s"],
                batch=TRAIN_BATCH, seq=TRAIN_SEQ, steps=TRAIN_STEPS,
                losses=losses(tr))


def data_parallel_phase(cfg, seed: int) -> dict:
    """4-chip data parallelism with bucketed overlap sync against the same
    steps and global batch on one chip."""
    import jax
    import numpy as np
    from repro.launch.mesh import initialize_distributed, make_distributed_mesh
    devices = jax.devices()
    check(len(devices) == DP_CHIPS,
          f"--four-chips needs {DP_CHIPS} devices, found {len(devices)}")
    initialize_distributed()            # one process: the identity
    mesh = make_distributed_mesh()
    check(dict(mesh.shape) == {"pod": 1, "data": DP_CHIPS, "model": 1},
          f"unexpected mesh {dict(mesh.shape)}")

    tr4, data = make_trainer(cfg, DP_BATCH, TRAIN_SEQ, DP_STEPS, seed,
                             overlap=True)
    with jax.set_mesh(mesh):
        params, opt = tr4.fit(data, seed=seed)
        # the step the trainer ran, compiled again for its partitioned
        # program: each chip computes on its own rows of the batch and the
        # gradients are summed across chips
        rows = {"tokens": np.zeros((DP_BATCH, TRAIN_SEQ), np.int32)}
        hlo = tr4._make_step().lower(params, opt, rows).compile().as_text()
    spans = {len(x.sharding.device_set) for x in jax.tree.leaves(params)}
    del params, opt                     # free the chips for the 1-chip run
    peaks4 = [peak_bytes(d) for d in devices]
    local_rows = f"s32[{DP_BATCH // DP_CHIPS},{TRAIN_SEQ}]"
    check(spans == {DP_CHIPS}, f"parameters span {spans} devices")
    check(local_rows in hlo and "all-reduce" in hlo,
          f"the 4-chip step has no per-chip batch rows {local_rows} or "
          f"no gradient all-reduce")

    tr1, data = make_trainer(cfg, DP_BATCH, TRAIN_SEQ, DP_STEPS, seed)
    tr1.fit(data, seed=seed)

    l4, l1 = losses(tr4), losses(tr1)
    rel = [abs(a - b) / abs(b) for a, b in zip(l4, l1)]
    check(len(l4) == len(l1) == DP_STEPS and max(rel) <= DP_LOSS_RTOL,
          f"4-chip losses {l4} vs 1-chip {l1}: rel diff {rel}")
    return dict(phase="data_parallel", chips=DP_CHIPS, mesh=dict(mesh.shape),
                overlap=True, global_batch=DP_BATCH, seq=TRAIN_SEQ,
                steps=DP_STEPS, compile_s_4chip=tr4.history[0]["wall_s"],
                compile_s_1chip=tr1.history[0]["wall_s"], losses_4chip=l4,
                losses_1chip=l1, loss_rel_diff=rel, loss_rtol=DP_LOSS_RTOL,
                param_spans=sorted(spans), per_chip_rows=local_rows,
                peak_bytes_in_use_4chip=peaks4)


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only 4-chip data-parallel training and its "
                         "1-chip comparison")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()

    import jax
    from repro.configs import get_config
    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    emit(phase="device", **device, compile_cache=cache)
    check(d0.platform == "tpu", f"no TPU: JAX found {device}")

    cfg = get_config(ARCH)
    phases = ([data_parallel_phase] if args.four_chips
              else [serve_phase, train_phase])
    for phase in phases:
        record = phase(cfg, args.seed)
        emit(**record, peak_bytes_in_use=peak_bytes(d0))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
