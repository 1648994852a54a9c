"""Per-op kernel registry: reference + Pallas implementations and their
tunable-parameter spaces (DESIGN.md §13).

The paper's efficiency layer picks the best implementation per device and
shape (MXNet §5's mshadow kernel templates; TensorFlow's per-device op
registries make the same move).  Here every Pallas kernel registers:

* ``impl`` — the Pallas entry point (what ``kernels/ops.py`` wraps),
* ``reference`` — the pure-jnp oracle (``kernels/ref.py``),
* ``tunables`` — schedule knobs and their candidate values (block sizes,
  pages-per-step, ...).  Knobs never change results, only the schedule,
* ``defaults`` — the hand-picked values call sites get with no tuning,
* ``bucket_of`` — the shape-bucketing function: real call shapes map to
  a coarse bucket string (dims rounded up to powers of two) so one tuned
  entry covers a band of nearby shapes instead of one exact shape,
* ``bench_cases`` — canned representative workloads the autotuner CLI
  and ``bench_kernels.py`` sweep.

``resolve`` is the single lookup path: explicit caller kwargs beat the
autotune cache, which beats the defaults — so every existing call site
gets tuned parameters with no signature change, and a hand-passed
``block_q=...`` still wins.

>>> pow2_bucket(300)
512
>>> sorted(ops())[:3]
['expert_gmm', 'flash_attention', 'paged_attention']
>>> resolve("rmsnorm", {"block_rows": None}, "rows=512,d=256,f32")
{'block_rows': 256}
>>> resolve("rmsnorm", {"block_rows": 64}, "rows=512,d=256,f32")
{'block_rows': 64}
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Any, Callable

import jax.numpy as jnp


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= n (bucket edge for a shape dim)."""
    n = max(1, int(n))
    return 1 << (n - 1).bit_length()


def _dt(dtype) -> str:
    """Short dtype tag for bucket strings (f32, bf16, i8, f8e4, ...)."""
    name = jnp.dtype(dtype).name
    return {"float32": "f32", "bfloat16": "bf16", "float16": "f16",
            "int8": "i8", "float8_e4m3fn": "f8e4",
            "float8_e5m2": "f8e5"}.get(name, name)


@dataclass(frozen=True)
class OpSpec:
    """One registered op (see module docstring for field semantics)."""
    name: str
    impl: Callable
    reference: Callable
    tunables: dict[str, tuple]
    defaults: dict[str, Any]
    bucket_of: Callable[..., str]
    bench_cases: tuple = ()     # ((label, make() -> (args, kwargs)), ...)

    def candidates(self) -> list[dict]:
        """Tunable cartesian product, defaults first (so a sweep always
        measures the untuned baseline)."""
        names = sorted(self.tunables)
        out = [dict(self.defaults)]
        for vals in itertools.product(*(self.tunables[n] for n in names)):
            c = dict(zip(names, vals))
            if c not in out:
                out.append(c)
        return out


_REGISTRY: dict[str, OpSpec] = {}


def register(spec: OpSpec) -> OpSpec:
    if spec.name in _REGISTRY:
        raise ValueError(f"op {spec.name!r} already registered")
    assert set(spec.defaults) == set(spec.tunables), spec.name
    _REGISTRY[spec.name] = spec
    return spec


def get(name: str) -> OpSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown op {name!r}; registered: {sorted(_REGISTRY)}"
                       ) from None


def ops() -> list[str]:
    return sorted(_REGISTRY)


def resolve(name: str, explicit: dict, bucket: str) -> dict:
    """Final tunable values for one call: defaults <- cached winner <-
    explicit non-None kwargs.  Returns a full params dict."""
    spec = get(name)
    params = dict(spec.defaults)
    from .autotune import cached_params       # lazy: autotune imports us
    won = cached_params(name, bucket)
    if won:
        params.update({k: v for k, v in won.items() if k in spec.tunables})
    params.update({k: v for k, v in explicit.items() if v is not None})
    return params


# ---------------------------------------------------------------------------
# registrations — one per Pallas kernel.  bench_cases build their arrays
# lazily (import-time stays allocation-free).

def _rand(key, shape, dtype=jnp.float32):
    import jax
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


def _register_all():
    from . import ref
    from .expert_gmm import expert_gmm
    from .flash_attention import flash_attention
    from .fused_update import sgd_momentum
    from .paged_attention import paged_attention
    from .rmsnorm import rmsnorm
    from .sampling import sample_tokens

    def flash_bucket(q, k, v, **kw):
        B, Sq, H, hd = q.shape
        Sk, K = k.shape[1], k.shape[2]
        return (f"B={pow2_bucket(B)},Sq={pow2_bucket(Sq)},"
                f"Sk={pow2_bucket(Sk)},H={H},K={K},hd={hd},{_dt(q.dtype)}")

    def flash_case(B, S, H, K, hd):
        def make():
            return ((_rand(0, (B, S, H, hd)), _rand(1, (B, S, K, hd)),
                     _rand(2, (B, S, K, hd))), {"causal": True})
        return make

    register(OpSpec(
        name="flash_attention", impl=flash_attention,
        reference=ref.flash_attention_ref,
        tunables={"block_q": (64, 128, 256), "block_k": (64, 128, 256)},
        defaults={"block_q": 128, "block_k": 128},
        bucket_of=flash_bucket,
        bench_cases=(("S256_gqa", flash_case(1, 256, 4, 2, 64)),
                     ("S512_gqa", flash_case(1, 512, 8, 2, 64)))))

    def paged_bucket(q, k_pages, v_pages, block_tables, lengths, layer,
                     **kw):
        B, H, hd = q.shape
        bs, K = k_pages.shape[2], k_pages.shape[3] // hd
        P = block_tables.shape[1]
        quant = "q" if kw.get("k_scale") is not None else ""
        return (f"B={pow2_bucket(B)},P={pow2_bucket(P)},bs={bs},H={H},"
                f"K={K},hd={hd},{_dt(k_pages.dtype)}{quant}")

    def paged_case(B, P, NB, bs, H, K, hd, L=2, kv_dtype=None):
        def make():
            import jax
            import numpy as np
            kp = _rand(1, (L, NB, bs, K, hd))
            vp = _rand(2, (L, NB, bs, K, hd))
            kw = {}
            if kv_dtype is not None:
                from .quant import kv_quantize_rows
                kp, kw["k_scale"] = kv_quantize_rows(kp, kv_dtype)
                vp, kw["v_scale"] = kv_quantize_rows(vp, kv_dtype)
            tables = jax.random.permutation(
                jax.random.PRNGKey(3),
                np.arange(1, NB))[:B * P].reshape(B, P).astype(jnp.int32)
            lengths = jnp.full((B,), P * bs - bs // 2, jnp.int32)
            lanes = (L, NB, bs, K * hd)
            return ((_rand(0, (B, H, hd)), kp.reshape(lanes),
                     vp.reshape(lanes), tables, lengths,
                     jnp.int32(L - 1)), kw)
        return make

    register(OpSpec(
        name="paged_attention", impl=paged_attention,
        reference=ref.paged_attention_ref,
        tunables={"pages_per_step": (1, 2, 4), "head_tile": (8, 16)},
        defaults={"pages_per_step": 1, "head_tile": 16},
        bucket_of=paged_bucket,
        bench_cases=(
            ("decode_B4", paged_case(4, 8, 40, 16, 8, 2, 64)),
            ("decode_B4_int8", paged_case(4, 8, 40, 16, 8, 2, 64,
                                          kv_dtype=jnp.int8)))))

    def gmm_bucket(x, wg, wu, wd, tile_group, n_tiles, **kw):
        M, D = x.shape
        E, _, F = wg.shape
        return (f"M={pow2_bucket(M)},tm={kw.get('block_rows')},D={D},F={F},"
                f"E={E},{_dt(x.dtype)}")

    def gmm_case(E, D, F, tm, sizes):
        def make():
            import numpy as np
            padded = [-(-n // tm) * tm for n in sizes]
            M = sum(padded) + tm
            tg = np.repeat(np.arange(E), [p // tm for p in padded])
            tg = np.concatenate([tg, np.full(M // tm - len(tg), E - 1)])
            return ((_rand(0, (M, D)), _rand(1, (E, D, F)) * D ** -0.5,
                     _rand(2, (E, D, F)) * D ** -0.5,
                     _rand(3, (E, F, D)) * F ** -0.5,
                     jnp.asarray(tg, jnp.int32),
                     jnp.int32(sum(padded) // tm)), {"block_rows": tm})
        return make

    register(OpSpec(
        name="expert_gmm", impl=expert_gmm,
        reference=ref.expert_gmm_ref,
        tunables={"block_ff": (128, 256, 512)},
        defaults={"block_ff": 256},
        bucket_of=gmm_bucket,
        bench_cases=(("E4_rows", gmm_case(4, 256, 512, 16, (9, 0, 30, 3))),)))

    def rmsnorm_bucket(x, weight, **kw):
        rows = 1
        for d in x.shape[:-1]:
            rows *= d
        return f"rows={pow2_bucket(rows)},d={x.shape[-1]},{_dt(x.dtype)}"

    def rmsnorm_case(rows, d):
        def make():
            return ((_rand(0, (rows, d)), _rand(1, (d,))), {})
        return make

    register(OpSpec(
        name="rmsnorm", impl=rmsnorm,
        reference=ref.rmsnorm_ref,
        tunables={"block_rows": (64, 256, 1024)},
        defaults={"block_rows": 256},
        bucket_of=rmsnorm_bucket,
        bench_cases=(("2048x512", rmsnorm_case(2048, 512)),
                     ("8192x512", rmsnorm_case(8192, 512)))))

    def sgd_bucket(param, grad, mom, **kw):
        return f"n={pow2_bucket(param.size)},{_dt(param.dtype)}"

    def sgd_case(n):
        def make():
            return ((_rand(0, (n,)), _rand(1, (n,)),
                     _rand(2, (n,))), {})
        return make

    register(OpSpec(
        name="sgd_momentum", impl=sgd_momentum,
        reference=ref.sgd_momentum_ref,
        tunables={"block": (16384, 65536, 262144)},
        defaults={"block": 65536},
        bucket_of=sgd_bucket,
        bench_cases=(("256k", sgd_case(1 << 18)),
                     ("1M", sgd_case(1 << 20)))))

    def sample_bucket(logits, u, **kw):
        B, V = logits.shape
        return f"B={pow2_bucket(B)},V={pow2_bucket(V)},{_dt(logits.dtype)}"

    def sample_case(B, V):
        def make():
            import jax
            u = jax.random.uniform(jax.random.PRNGKey(9), (B,))
            return ((_rand(0, (B, V)) * 3.0, u),
                    {"temperature": 0.8, "top_k": 50, "top_p": 0.9})
        return make

    register(OpSpec(
        name="sample_tokens", impl=sample_tokens,
        reference=ref.sample_ref,
        tunables={"rows_per_step": (1, 4, 8)},
        defaults={"rows_per_step": 8},
        bucket_of=sample_bucket,
        bench_cases=(("B8_V512", sample_case(8, 512)),
                     ("B16_V2048", sample_case(16, 2048)))))


_register_all()
