"""What every cell of the chip benchmark shares: the checkout's layout,
the cell's data files and reference module found by name, the device and
its peaks, the compile cache, the weights made from the seed, and the
result line.

Nothing here imports the system under test at module level; the drivers
import it once the device has been checked.  Nothing here names an
architecture either: what is particular to one (its sizes, its weight
rule, its per-token counts, the file keys the program takes) lies in the
reference module its configuration file names.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys
import time
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# fixed path inside the checkout: the cache directory is part of JAX's key
COMPILE_CACHE = ROOT / ".jax_cache"


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, unknown device, bad data
    file).  ``run_cell`` exits nonzero on it and prints no result line."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def log(**record) -> None:
    """An earlier line of standard output (never the last)."""
    print(json.dumps(record, default=float), flush=True)


def load_module(path: Path, prefix: str) -> ModuleType:
    """A module of the benchmark's data (a reader, a reference), loaded by
    path under a name of its own."""
    if not path.is_file():
        raise BenchError(f"no file {path}")
    name = prefix + re.sub(r"[^A-Za-z0-9_]", "_", path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(name: str, root: Path = ROOT) -> ModuleType:
    """The plain reference a configuration file names under
    ``"reference"``: ``<root>/bench/reference/<name>.py``.  It gives
    ``dims_of``, ``logits_at``, ``loss_and_grad``, ``leaf_rule``,
    ``PROGRAM_KEYS`` and the per-token counts ``matmul_flops_per_token``,
    ``attn_layers`` and ``kv_bytes_per_token``."""
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", str(name)):
        raise BenchError(f"reference {name!r} is not a module name")
    return load_module(root / "bench" / "reference" / f"{name}.py",
                       "bench_reference_")


# ---------------------------------------------------------------------------
# the cell, found by name


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    reference: ModuleType  # bench/reference/<config's "reference">.py
    mix: dict             # bench/mixes/<traffic>.json
    limits: dict          # bench/limits/<workload>.json
    end_to_end: list      # BENCHMARK.json metrics this cell reports
    per_layer: list

    @property
    def dims(self) -> dict:
        """The sizes the reference and the counters read, from the
        configuration file."""
        return self.reference.dims_of(self.config)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files, all
    found under ``<root>/bench`` by the names there."""
    bench = load_json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; "
                         f"have {sorted(work)}")
    w = work[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if ("workloads" in m and name in m["workloads"])
                 or ("workloads" not in m and m["moves"] in names)]
    config = load_json(root / cfg_entry["file"])
    if "reference" not in config:
        raise BenchError(f"{cfg_entry['file']} names no reference module")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                reference=load_reference(config["reference"], root),
                mix=load_json(root / "bench" / "mixes"
                              / f"{w['traffic']}.json"),
                limits=load_json(root / "bench" / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


# ---------------------------------------------------------------------------
# device


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"device_kind {kind!r} is not in bench/peaks.json; "
                         f"known: {sorted(table)}")
    return table[kind]


def check_device(chips: int) -> dict:
    """The device as JAX reports it.  No TPU, or fewer chips than the cell
    asks for, is an error: a CPU number is never a device metric."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}
    if d0.platform != "tpu":
        raise BenchError(f"no TPU: JAX found {info}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {info}")
    peaks_for(d0.device_kind)
    info["count"] = chips
    return info


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def use_compile_cache() -> str:
    """JAX's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else a fixed directory inside the checkout.  Every program is
    cached, however quick to compile, so only a cell's first run in a
    checkout compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(COMPILE_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def use_src_path(root: Path = ROOT) -> None:
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


# ---------------------------------------------------------------------------
# model configuration, checked against the file that states it

# keys of a file's ``model`` block that every architecture has -> the
# repo's ArchConfig fields; the reference module adds its own
# (``PROGRAM_KEYS``)
COMMON_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "vocab_size": "vocab",
    "tie_word_embeddings": "tie_embeddings",
    "dtype": "dtype",
    "norm_eps": "norm_eps",
}


def arch_config(conf: dict, ref: ModuleType):
    """The repo's ArchConfig for a configuration file: the repo's own
    config with every key of the file's ``model`` block that the program
    takes (``COMMON_KEYS`` and the reference's ``PROGRAM_KEYS``) applied,
    then checked key by key, so the file states what runs.  The cache's
    bytes the file states are checked against the program's shapes."""
    from repro.configs import get_config
    keys = {**COMMON_KEYS, **ref.PROGRAM_KEYS}
    cfg = get_config(conf["arch"])
    model = conf["model"]
    over = {keys[k]: model[k] for k in model if k in keys}
    cfg = dataclasses.replace(cfg, **over)
    for key, field in keys.items():
        if key not in model:
            continue
        have = getattr(cfg, field) if field != "head_dim" else cfg.hd
        if have != model[key]:
            raise BenchError(f"{conf['name']}: {key}={model[key]} in the "
                             f"file but {field}={have} in the program")
    if "mlp" in model:
        mlps = sorted({spec.mlp for spec in cfg.pattern})
        if mlps != [model["mlp"]]:
            raise BenchError(f"{conf['name']}: mlp {model['mlp']!r} in the "
                             f"file but {mlps} in the program")
    pool = conf["kv_pool"]
    per_token = kv_bytes_per_token(cfg)
    if pool["bytes_per_token"] != per_token:
        raise BenchError(f"{conf['name']}: kv bytes/token "
                         f"{pool['bytes_per_token']} in the file, "
                         f"{per_token} from the shapes")
    per_lane = state_bytes_per_lane(cfg)
    if per_lane or "state_bytes_per_lane" in pool:
        if pool.get("state_bytes_per_lane") != per_lane:
            raise BenchError(f"{conf['name']}: state bytes/lane "
                             f"{pool.get('state_bytes_per_lane')} in the "
                             f"file, {per_lane} from the program's slots")
    return cfg


def kv_bytes_per_token(cfg) -> int:
    """Attention layers x (k, v) x kv heads x head_dim x bytes of the
    served dtype; other layers keep nothing per token."""
    item = 2 if cfg.dtype == "bfloat16" else 4
    attn = sum(spec.kind == "attn" for spec in cfg.pattern) * cfg.n_super
    return attn * 2 * cfg.n_kv_heads * cfg.hd * item


def state_bytes_per_lane(cfg) -> int:
    """Bytes of one lane's recurrent state (conv and SSM) in the program's
    paged cache: the slot arrays of its non-attention layers, per slot."""
    kinds = [spec.kind for spec in cfg.pattern]
    if all(k == "attn" for k in kinds):
        return 0
    import jax
    from repro.models import get_model
    lanes = 2
    cache = jax.eval_shape(
        lambda: get_model(cfg).make_paged_cache(2, 16, lanes))["layers"]
    total = sum(x.size * x.dtype.itemsize
                for i, k in enumerate(kinds) if k != "attn"
                for x in jax.tree.leaves(cache[f"p{i}"]))
    return total // lanes


# ---------------------------------------------------------------------------
# weights, made on the device from the seed in one jitted call


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def leaf_path(path) -> tuple:
    """A parameter leaf's whole path as a tuple of names:
    ``('blocks', 'p0', 'attn', 'wq')``."""
    return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def make_params(cfg, seed: int, ref: ModuleType, d: dict, sharding=None):
    """The program's parameter tree, filled from ``seed`` on the device in
    the dtype it is served in.  Each leaf is drawn normal with the mean
    and standard deviation that the reference's ``leaf_rule`` gives for
    its whole path; one key per leaf, split in the tree's order."""
    import jax
    import jax.numpy as jnp
    from repro.models import get_model
    shapes = jax.eval_shape(get_model(cfg).init, jax.random.PRNGKey(0))
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [s for _, s in paths]
    rules = [ref.leaf_rule(leaf_path(p), s.shape, d) for p, s in paths]

    def fill(key):
        keys = jax.random.split(key, len(specs))
        leaves = []
        for k, (mean, std), s in zip(keys, rules, specs):
            x = jax.random.normal(k, s.shape, jnp.float32) * std
            if mean:
                x = x + mean
            leaves.append(x.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    kw = {} if sharding is None else {"out_shardings": sharding}
    params = jax.jit(fill, **kw)(seed_key(seed))
    return jax.block_until_ready(params)


# ---------------------------------------------------------------------------
# the check and the result line


def within(readings: dict, limits: dict) -> bool:
    """Whether every number a limits file names is at or under its
    limit: ``correct`` for the program's readings, and for the control's,
    which has to come out false."""
    return all(readings[k] <= v for k, v in limits.items())


def program_spans() -> list[dict]:
    """The program's host spans, each with ``t_abs`` on the harness's
    clock (``time.perf_counter``)."""
    from repro import obs
    rec = obs.get_recorder()
    epoch_s = -rec.to_us(0.0) / 1e6
    return [dict(e, t_abs=epoch_s + e["ts"] / 1e6) for e in rec.events()]


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: dict,
                breakdown: dict | None = None) -> str:
    """The last line of standard output.  ``checks`` (each number compared
    beside its limit) comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out, default=float)


class Window:
    """The measured window on the host clock, and the optional profiler
    trace over it."""

    def __init__(self, clock, trace_dir=None):
        self.clock, self.trace_dir = clock, trace_dir
        self.t0 = self.t1 = None

    def open(self):
        if self.trace_dir is not None:
            import jax
            from repro import obs
            obs.enable(True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
        self.t0 = self.clock.now()

    def close(self):
        self.t1 = self.clock.now()
        if self.trace_dir is not None:
            import jax
            from repro import obs
            jax.profiler.stop_trace()
            obs.enable(False)


class Clock:
    """Host clock of the harness (a test swaps in a fake one)."""

    def __init__(self):
        self.now = time.perf_counter
        self.sleep = time.sleep
