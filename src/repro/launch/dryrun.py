import os
# default to the 512-chip dry-run topology, preserving any other XLA flags
# the caller set — but never clobber an explicit device count (tests and
# benches import this module for its parsers after setting up smaller meshes)
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run (deliverable e) — a CPU tool.

It builds its 256/512-device meshes from virtual CPU devices (the
``XLA_FLAGS`` above) and pins itself to the CPU platform, so on a machine
with a chip it never takes the chip nor resolves to one device.

For every (architecture × input shape) pair, lower + compile the step
function on the production meshes (16×16 single-pod, 2×16×16 multi-pod),
print/record memory_analysis (proves it fits) and cost_analysis
(FLOPs/bytes for §Roofline), and parse collective bytes out of the
compiled HLO.

Roofline probes: cost_analysis counts a lax.scan body once, so per-layer
costs come from compiling 1- and 2-superblock UNROLLED variants with
identical shardings; total = probe1 + (n_super-1) * (probe2 - probe1).

Usage:
  python -m repro.launch.dryrun --arch dbrx-132b --shape train_4k
  python -m repro.launch.dryrun --all            # every pair, both meshes
  python -m repro.launch.dryrun --all --mesh single --no-probes
"""
import argparse
import json
import re
import time
from dataclasses import replace
from pathlib import Path

import jax

from repro.configs import ARCH_IDS, LONG_CONTEXT_ARCHS, get_config
from repro.dist import (batch_pspecs, cache_pspecs, make_shardings,
                        param_pspecs)
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (input_specs, make_decode_step,
                                make_prefill_step, make_train_step)
from repro.models import INPUT_SHAPES
from jax.sharding import PartitionSpec as P, NamedSharding

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s+(?P<ty>\(?[a-z0-9\[\],{}\s]+\)?)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?\((?P<rest>[^\n]*)")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUP_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUP_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _group_size(rest: str) -> int:
    """Shard-group size of one collective (iota or explicit list form)."""
    m = _GROUP_RE.search(rest)
    if m:
        return int(m.group(2))
    m = _GROUP_LIST_RE.search(rest)
    if m:
        return len(m.group(1).split(","))
    return 1


def collective_bytes(hlo_text: str) -> dict:
    """Per-device link-bytes estimate for every collective in an HLO dump.

    From each instruction's RESULT bytes S and replica-group size g
    (ring-algorithm accounting):
      all-gather        S·(g-1)/g        (result = gathered)
      all-reduce        2·S·(g-1)/g
      reduce-scatter    S·(g-1)          (result = scattered shard)
      all-to-all        S·(g-1)/g
      collective-permute S
    ``raw`` keeps the plain result-bytes sums for reference.
    """
    out = {"all-reduce": 0.0, "all-gather": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0}
    raw = dict.fromkeys(out, 0)
    counts = dict.fromkeys(out, 0)
    for m in _COLL_RE.finditer(hlo_text):
        op = m.group("op")
        g = _group_size(m.group("rest"))
        total = 0
        for dt, dims in _SHAPE_RE.findall(m.group("ty")):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        raw[op] += total
        counts[op] += 1
        if op == "all-gather":
            moved = total * (g - 1) / max(g, 1)
        elif op == "all-reduce":
            moved = 2 * total * (g - 1) / max(g, 1)
        elif op == "reduce-scatter":
            moved = total * (g - 1)
        elif op == "all-to-all":
            moved = total * (g - 1) / max(g, 1)
        else:
            moved = total
        out[op] += moved
    out["total"] = sum(out.values())
    out["raw"] = raw
    out["raw_total"] = sum(raw.values())
    out["counts"] = counts
    return out


def _step_and_specs(cfg, shape_name, mesh):
    """Build (step_fn, kwargs specs, in_shardings, donate) for a shape."""
    from repro.perf_flags import FLAGS
    shp = INPUT_SHAPES[shape_name]
    specs = input_specs(cfg, shape_name)
    # sequence sharding: full-sequence batches enter S-sharded over "model"
    # (DESIGN.md §8) so the ring path never gathers the sequence
    bkind = ("seq" if FLAGS.seq_shard and shp.kind in ("train", "prefill")
             else shp.kind)
    p_sh = make_shardings(mesh, param_pspecs(cfg, specs["params"], mesh))
    b_sh = make_shardings(mesh, batch_pspecs(cfg, specs["batch"], mesh,
                                             bkind))
    repl = NamedSharding(mesh, P())
    if shp.kind == "train":
        step = make_train_step(cfg)
        o_sh = {"mom": jax.tree.map(lambda s: s, p_sh), "step": repl}
        in_sh = (p_sh, o_sh, b_sh)
        out_sh = (p_sh, o_sh, jax.tree.map(lambda _: repl,
                                           jax.eval_shape(
                                               step, specs["params"],
                                               specs["opt_state"],
                                               specs["batch"])[2]))
        args = (specs["params"], specs["opt_state"], specs["batch"])
        donate = (0, 1)
    elif shp.kind == "prefill":
        step = make_prefill_step(cfg)
        out_shapes = jax.eval_shape(step, specs["params"], specs["batch"])
        logits_sh = repl
        c_sh = make_shardings(mesh, cache_pspecs(cfg, out_shapes[1], mesh))
        in_sh = (p_sh, b_sh)
        out_sh = (logits_sh, c_sh)
        args = (specs["params"], specs["batch"])
        donate = ()
    else:  # decode
        step = make_decode_step(cfg)
        c_sh = make_shardings(mesh, cache_pspecs(cfg, specs["cache"], mesh))
        in_sh = (p_sh, c_sh, b_sh)
        out_sh = (NamedSharding(mesh, P()), c_sh)
        args = (specs["params"], specs["cache"], specs["batch"])
        donate = (1,)
    return step, args, in_sh, out_sh, donate


class _CompiledCompat:
    """Delegating wrapper normalizing ``cost_analysis()`` to the modern
    dict form (older jax returns a one-dict-per-program list)."""

    def __init__(self, compiled):
        self._compiled = compiled

    def __getattr__(self, name):
        return getattr(self._compiled, name)

    def cost_analysis(self):
        ca = self._compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return ca or {}


def lower_and_compile(cfg, shape_name, mesh):
    step, args, in_sh, out_sh, donate = _step_and_specs(cfg, shape_name, mesh)
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = _CompiledCompat(lowered.compile())
        t_compile = time.time() - t0
    return lowered, compiled, t_lower, t_compile


def analyze(compiled):
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    coll = collective_bytes(txt)
    return {
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device": (mem.argument_size_in_bytes
                                + mem.output_size_in_bytes
                                + mem.temp_size_in_bytes
                                - mem.alias_size_in_bytes),
        },
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
        "collectives": coll,
    }


def probe_cfg(cfg, n_super):
    return replace(cfg, n_layers=len(cfg.pattern) * n_super)


def run_pair(arch: str, shape_name: str, multi_pod: bool,
             probes: bool = True, verbose: bool = True,
             seq_shard: bool = False, pp_stages: int = 1,
             microbatches: int = 1) -> dict:
    long_ctx = shape_name.startswith("long_500k")
    if long_ctx and arch not in LONG_CONTEXT_ARCHS and not seq_shard:
        return {"arch": arch, "shape": shape_name, "status": "SKIP",
                "reason": "pure full-attention arch; long_500k requires "
                          "sub-quadratic attention (DESIGN.md §5) or the "
                          "sequence-sharded ring path (--seq-shard, §8)"}
    cfg = get_config(arch, long_context=long_ctx, seq_shard=seq_shard)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "n_layers": cfg.n_layers, "n_super": cfg.n_super,
           "params": cfg.param_count(),
           "params_active": cfg.param_count(active_only=True),
           "seq_shard": seq_shard,
           "status": "OK"}
    shp = INPUT_SHAPES[shape_name]
    if shp.kind == "decode":
        # true vs padded serving-cache footprint (DESIGN.md §9): the dense
        # engine pays B x max_len rectangles; a paged cache pays only live
        # blocks — reported at full occupancy and at the S/2 mean of a
        # steady-state mixed-traffic batch
        from repro.core.memplan import (kv_cache_bytes_dense,
                                        kv_cache_bytes_paged)
        bs = 16
        B, S = shp.global_batch, shp.seq_len
        dense = kv_cache_bytes_dense(cfg, B, S)
        full = kv_cache_bytes_paged(cfg, [S] * B, bs)
        half = kv_cache_bytes_paged(cfg, [S // 2] * B, bs)
        # quantized pools (int8 codes + per-row f32 scales, DESIGN.md §13)
        full_q = kv_cache_bytes_paged(cfg, [S] * B, bs, kv_dtype="int8")
        rec["cache_footprint"] = {
            "block_size": bs,
            "dense_bytes": dense,
            "paged_bytes_full": full["bytes"],
            "paged_bytes_mixed_mean": half["bytes"],
            "padded_over_true_mixed": round(dense / max(half["bytes"], 1), 2),
            "paged_bytes_full_int8": full_q["bytes"],
            "fp_over_int8": round(full["bytes"] / max(full_q["bytes"], 1), 2),
        }
    if pp_stages > 1 and shp.kind == "train":
        # per-stage param/activation memplan of the 1F1B pipeline
        # (DESIGN.md §10): what each "stage" shard holds, the saved
        # microbatch residuals, and the activation hand-off bytes
        from repro.core.memplan import pipeline_stage_bytes
        n_data = (16 // pp_stages) * (2 if multi_pod else 1)
        rec["pipeline"] = pipeline_stage_bytes(
            cfg, n_stages=pp_stages, microbatches=microbatches,
            global_batch=shp.global_batch, seq_len=shp.seq_len,
            n_data=n_data)
        if verbose:
            p = rec["pipeline"]
            print(f"  [pipeline pp={pp_stages} M={microbatches}] "
                  f"stage params {p['stage_param_bytes']/2**30:.2f} GiB "
                  f"saved acts {p['stage_activation_bytes']/2**30:.2f} GiB "
                  f"bubble {p['bubble_fraction']:.3f}")
    from repro.perf_flags import FLAGS, set_flags
    prev_flags = (FLAGS.seq_shard, FLAGS.attn_impl)
    if seq_shard:
        set_flags(seq_shard=True, attn_impl="auto")
    try:
        lowered, compiled, t_l, t_c = lower_and_compile(cfg, shape_name, mesh)
        rec["full"] = analyze(compiled)
        rec["t_lower_s"] = round(t_l, 2)
        rec["t_compile_s"] = round(t_c, 2)
        if verbose:
            m = rec["full"]["memory"]
            print(f"  [{mesh_name}] lower {t_l:.1f}s compile {t_c:.1f}s "
                  f"peak/device {m['peak_per_device']/2**30:.2f} GiB "
                  f"coll {rec['full']['collectives']['total']/2**20:.1f} MiB")
        if probes:
            # 2- and 4-superblock UNROLLED probes (1-layer graphs trigger
            # partitioner edge cases; differences over {2,4} are stable)
            for n in (2, 4):
                if cfg.n_super < n:
                    continue
                _, c2, _, _ = lower_and_compile(probe_cfg(cfg, n),
                                                shape_name, mesh)
                rec[f"probe{n}"] = analyze(c2)
    except Exception as e:  # noqa: BLE001 — record failures, they are bugs
        rec["status"] = "FAIL"
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
        if verbose:
            print(f"  [{mesh_name}] FAILED: {rec['error'][:200]}")
    finally:
        # restore only what we set — callers may hold other tuned flags
        set_flags(seq_shard=prev_flags[0], attn_impl=prev_flags[1])
    return rec


def save(rec: dict):
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    ring = "__ring" if rec.get("seq_shard") else ""
    name = f"{rec['arch']}__{rec['shape']}__{rec.get('mesh', 'skip')}{ring}.json"
    (OUT_DIR / name).write_text(json.dumps(rec, indent=1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--seq-shard", action="store_true",
                    help="sequence-sharded batches + ring attention "
                         "(PerfFlags.seq_shard; unlocks long_500k for "
                         "full-attention archs — DESIGN.md §8)")
    ap.add_argument("--pp-stages", type=int, default=1,
                    help="report the per-stage pipeline memplan (param/"
                         "activation bytes per 'stage' shard; DESIGN.md "
                         "§10) for train shapes")
    ap.add_argument("--microbatches", type=int, default=8,
                    help="micro-batch count for the --pp-stages memplan")
    ap.add_argument("--force", action="store_true",
                    help="recompute even if a result JSON exists")
    args = ap.parse_args()
    jax.config.update("jax_platforms", "cpu")   # virtual devices only

    pairs = ([(a, s) for a in ARCH_IDS for s in INPUT_SHAPES]
             if args.all else [(args.arch, args.shape)])
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = 0
    ring = "__ring" if args.seq_shard else ""
    for arch, shape in pairs:
        for mp in meshes:
            mesh_name = "2x16x16" if mp else "16x16"
            out = OUT_DIR / f"{arch}__{shape}__{mesh_name}{ring}.json"
            skip_name = OUT_DIR / f"{arch}__{shape}__skip.json"
            # a stale default-run skip (full attention × long_500k) must
            # not block the --seq-shard run that exists to unlock the pair
            skipped = skip_name.exists() and not args.seq_shard
            if not args.force and (out.exists() or skipped):
                continue
            print(f"== {arch} × {shape} × {mesh_name}"
                  + (" (seq-shard/ring)" if args.seq_shard else ""))
            # probes only needed on the single-pod mesh (roofline table)
            rec = run_pair(arch, shape, mp,
                           probes=(not args.no_probes) and not mp,
                           seq_shard=args.seq_shard,
                           pp_stages=args.pp_stages,
                           microbatches=args.microbatches)
            save(rec)
            failures += rec["status"] == "FAIL"
            if rec["status"] == "SKIP":
                break  # skip applies to both meshes
    print(f"done; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
