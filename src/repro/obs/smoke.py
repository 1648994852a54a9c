"""Observability smoke: exercise every instrumented layer on CPU, export
the trace + metrics artifacts, and assert the trace is a valid Chrome
trace-event document carrying >= 1 span from each layer.

CI runs this (instead of tracing the full bench suite — tracing would
perturb fig6's executor/eager timing-ratio gates) to produce the
``--trace``/``--metrics`` artifacts and gate the instrumentation:

  PYTHONPATH=src python -m repro.obs.smoke \
      --trace /tmp/trace.json --metrics /tmp/metrics.jsonl

Exercised layers -> expected spans:

* dependency engine (``core/engine.py``)  -> cat ``engine`` op spans;
* trainer (``train/trainer.py``)          -> cat ``train`` step spans;
* serving (``serve/engine.py``)           -> cat ``serve`` lifecycle
  spans (queued / prefill_chunk / decode per admitted request);
* dist (``dist/ring.py``, ``dist/pipeline.py``, ``dist/collectives.py``)
  -> cat ``jit-trace`` named-scope spans (``ring_fwd_*``, ``pp_fwd_*``,
  ``grad_sync_*``) recorded while the schedules stage.

Exit 1 with a per-layer report when any expectation fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# a CPU tool: the pipeline schedule needs a real multi-device "stage"
# axis, made of virtual CPU devices that must be requested before jax
# initializes (same trick as benchmarks/bench_dist.py); main() pins the
# CPU platform so a machine with a chip never hands it one chip instead
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=2")


def _engine_layer():
    """A tiny RAW/WAR/WAW chain through a fresh default engine."""
    from repro.core.engine import Tag, reset_default_engine
    eng = reset_default_engine()
    a, b = Tag("a"), Tag("b")
    eng.push(lambda: None, writes=(a,), name="init_a")
    eng.push(lambda: None, reads=(a,), writes=(b,), name="b_from_a")
    eng.push(lambda: None, reads=(a,), writes=(a,), name="update_a")
    eng.wait_all()
    eng.publish_stats()


def _train_layer(cfg):
    from repro.data import SyntheticLM
    from repro.train import TrainConfig, Trainer
    tcfg = TrainConfig(total_steps=2, warmup_steps=1)
    data = SyntheticLM(cfg.vocab, 16, 2, n_batches=2)
    Trainer(cfg, tcfg).fit(iter(data))


def _serve_layer(cfg, params):
    import numpy as np
    from repro.serve import PagedServeEngine
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, cfg.vocab, L)) for L in (5, 9, 17)]
    eng = PagedServeEngine(cfg, params, block_size=8, max_batch=2,
                           max_len=48, prefill_chunk=8)
    eng.generate(prompts, max_new_tokens=[3, 4, 5])


def _dist_layer():
    import jax
    import jax.numpy as jnp
    from repro.dist import make_mesh
    from repro.dist.collectives import gradient_sync
    from repro.dist.pipeline import pipeline_stack
    from repro.dist.ring import ring_attention

    # ring: the 1-shard fallback still walks the _ring_fwd schedule
    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (1, 8, 2, 4))
    kv = jax.random.normal(k, (1, 8, 1, 4))
    ring_attention(q, kv, kv)

    n_dev = len(jax.devices())
    if n_dev < 2:   # single-device jax: ring scopes alone cover the layer
        return

    # pipeline: 2 stages x 2 microbatches over a forced host-device mesh
    mesh = make_mesh((2,), ("stage",))
    params = {"w": jnp.eye(4)[None].repeat(2, 0)}

    def stage_fn(p, x):
        def body(h, w):
            return jnp.tanh(h @ w), 0.0
        h, _ = jax.lax.scan(body, x, p["w"])
        return h, {"aux": jnp.zeros((), jnp.float32)}

    x = jax.random.normal(k, (2, 4, 4))
    with jax.set_mesh(mesh):
        pipeline_stack(stage_fn, params, x, microbatches=2, mesh=mesh)

    # bucketed gradient sync: per-bucket collective chains
    dmesh = make_mesh((2,), ("data",))
    gradient_sync(dmesh, {"w": jnp.ones((2, 5))}, mode="bucketed")


LAYERS = {
    "engine": lambda spans: any(e["cat"] == "engine" for e in spans),
    "train": lambda spans: any(e["cat"] == "train" for e in spans),
    "serve-lifecycle": lambda spans: all(
        any(e["cat"] == "serve" and e["name"] == n for e in spans)
        for n in ("queued", "prefill_chunk", "decode")),
    "dist-named-scopes": lambda spans: any(
        e["cat"] == "jit-trace" and e["name"].startswith(
            ("ring_fwd_", "pp_fwd_", "grad_sync_"))
        for e in spans),
}


def check_trace(path: str) -> list[str]:
    """Validate the exported document; returns failure strings."""
    failures = []
    with open(path) as f:
        doc = json.load(f)          # malformed JSON raises -> crash is fine
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        return [f"{path}: no traceEvents"]
    for e in events:
        missing = {"name", "ph", "pid"} - set(e)
        if missing:
            failures.append(f"event {e} lacks {sorted(missing)}")
        if e.get("ph") == "X" and not {"ts", "dur"} <= set(e):
            failures.append(f"complete event {e['name']} lacks ts/dur")
    spans = [e for e in events if e.get("ph") == "X"]
    for layer, ok in LAYERS.items():
        n = "yes" if ok(spans) else "MISSING"
        print(f"  layer {layer}: {n}")
        if n == "MISSING":
            failures.append(f"no span for instrumented layer {layer!r}")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", metavar="PATH", required=True)
    ap.add_argument("--metrics", metavar="PATH", default=None)
    args = ap.parse_args()
    import jax
    jax.config.update("jax_platforms", "cpu")

    from repro import obs
    from repro.configs import get_config
    from repro.models import get_model, reduced
    obs.enable()

    cfg = reduced(get_config("qwen1.5-0.5b"))
    print("== engine layer")
    _engine_layer()
    print("== train layer")
    _train_layer(cfg)
    print("== serve layer")
    import jax
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    _serve_layer(cfg, params)
    print("== dist layer")
    _dist_layer()

    if args.metrics:
        n = obs.get_metrics().dump_jsonl(args.metrics)
        print(f"metrics: {args.metrics} ({n} metrics)")
    obs.export(args.trace)
    print(f"trace: {args.trace}")

    failures = check_trace(args.trace)
    # the serving histograms must have real samples, not just names
    snap = obs.get_metrics().snapshot()
    for name in ("serve.ttft_s", "serve.tpot_s", "serve.queue_wait_s"):
        if snap.get(name, {}).get("count", 0) < 1:
            failures.append(f"metric {name} recorded no samples")
    if failures:
        for f in failures:
            print(f"FAIL: {f}")
        sys.exit(1)
    print("PASS")


if __name__ == "__main__":
    main()
