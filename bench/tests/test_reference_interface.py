"""A configuration brings its own plain reference: the harness reaches it
only through the cell.  For the dense decoder the weights, counters and
gaps are what the harness computed before it named no architecture
(the rule it used is kept inline here); a toy module for another
architecture, under a root of its own, loads and fills weights with no
harness file edited."""
from __future__ import annotations

import json
import re
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, counters, traffic
from bench.reference import check, decoder
from bench.tests import tiny

common.use_src_path()

DATA = Path(__file__).resolve().parent / "data"
CELLS = {"qwen1.5-0.5b": "qwen1.5-0.5b.chat",
         "starcoder2-15b-pp4": "starcoder2-15b-pp4.code-backlog"}


# ---------------------------------------------------------------------------
# the harness's weight rule and counters as they were, keyed on names


def _old_leaf_scale(name, cfg):
    D, F = cfg.d_model, cfg.d_ff
    table = {"embed": 0.02, "lm_head": D ** -0.5,
             "wq": D ** -0.5, "wk": D ** -0.5, "wv": D ** -0.5,
             "wo": (cfg.n_heads * cfg.hd) ** -0.5,
             "wg": D ** -0.5, "wu": D ** -0.5, "wd": F ** -0.5,
             "bq": 0.1, "bk": 0.1, "bv": 0.1,
             "ln1": 0.1, "ln2": 0.1, "final_norm": 0.1}
    return table[name]


def _old_make_params(cfg, seed):
    from repro.models import get_model
    shapes = jax.eval_shape(get_model(cfg).init, jax.random.PRNGKey(0))
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [str(getattr(p[-1], "key", p[-1])) for p, _ in paths]
    specs = [s for _, s in paths]

    def fill(key):
        keys = jax.random.split(key, len(specs))
        leaves = [(jax.random.normal(k, s.shape, jnp.float32)
                   * _old_leaf_scale(n, cfg)).astype(s.dtype)
                  for k, n, s in zip(keys, names, specs)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(fill)(common.seed_key(seed))


def _old_matmul(d):
    D, H, K, hd, F = d["D"], d["H"], d["K"], d["hd"], d["F"]
    attn = D * H * hd + 2 * D * K * hd + H * hd * D
    mlp = (3 if d["mlp"] == "swiglu" else 2) * D * F
    return 2.0 * (d["L"] * (attn + mlp) + D * d["V"])


def _old_attn(d, ctx):
    return 4.0 * d["L"] * d["H"] * d["hd"] * ctx


def _old_counts(d, start, n, ctxs, seq):
    ctx_sum = n * start + n * (n + 1) / 2
    L, H, K, hd = d["L"], d["H"], d["K"], d["hd"]
    live = float(sum(ctxs))
    return {"prefill": n * _old_matmul(d) + _old_attn(d, ctx_sum),
            "decode": _old_matmul(d) + _old_attn(d, ctxs[0]),
            "train": 3.0 * (_old_matmul(d) + _old_attn(d, (seq + 1) / 2)),
            "paged": (4.0 * L * H * hd * live,
                      2.0 * L * K * hd * 2 * live
                      + 2.0 * L * len(ctxs) * H * hd * 2)}


def _new_counts(ref, d, start, n, ctxs, seq):
    return {"prefill": counters.prefill_flops(ref, d, start, n),
            "decode": counters.decode_flops(ref, d, ctxs[0]),
            "train": counters.train_flops_per_token(ref, d, seq),
            "paged": counters.paged_attn_cost(ref, d, ctxs)}


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint16 if x.dtype.itemsize == 2 else np.uint32)


@pytest.mark.parametrize("conf_name", sorted(CELLS))
def test_weights_are_bit_identical_to_the_rule_by_name(conf_name):
    cell = tiny.tiny_cell(CELLS[conf_name])
    cfg = common.arch_config(cell.config, cell.reference)
    assert cfg.dtype == "bfloat16"
    for seed in (7, 2**33 + 5):
        new = common.make_params(cfg, seed, cell.reference, cell.dims)
        old = _old_make_params(cfg, seed)
        flat_new = jax.tree_util.tree_flatten_with_path(new)[0]
        flat_old = dict(jax.tree_util.tree_flatten_with_path(old)[0])
        assert len(flat_new) == len(flat_old)
        for path, leaf in flat_new:
            np.testing.assert_array_equal(_bits(leaf), _bits(flat_old[path]),
                                          err_msg=str(path))


@pytest.mark.parametrize("conf_name", sorted(CELLS))
@pytest.mark.parametrize("size", ["tiny", "published"])
def test_counters_equal_the_dense_formulas(conf_name, size):
    if size == "tiny":
        cell = tiny.tiny_cell(CELLS[conf_name])
    else:
        cell = common.load_cell(CELLS[conf_name])
    d = cell.dims
    for start, n, ctxs, seq in [(0, 512, [7, 300, 4096], 4096),
                                (1536, 37, [1], 64)]:
        assert (_new_counts(cell.reference, d, start, n, ctxs, seq)
                == _old_counts(d, start, n, ctxs, seq))


def _serve_samples(cell, seed):
    """(prompt, served tokens) pairs of the tiny cell's own sizes."""
    rng = np.random.default_rng(seed)
    reqs = traffic.make_requests(cell.mix, cell.dims["V"], seed, 2.0)[:4]
    return [(r.prompt, list(rng.integers(0, cell.dims["V"], r.max_new)))
            for r in reqs]


@pytest.mark.parametrize("workload", sorted(CELLS.values()))
def test_serving_gaps_through_the_cell_equal_the_decoders(workload):
    cell = tiny.tiny_cell(workload)
    assert cell.reference is not decoder          # loaded by path
    cfg = common.arch_config(cell.config, cell.reference)
    params = common.make_params(cfg, 3, cell.reference, cell.dims)
    samples = _serve_samples(cell, 3)
    kw = dict(max_out=cell.mix["output"]["max"], seed=3, control=True)
    got = check.serve_gaps(cell.reference, params, samples, cell.dims,
                           cell.mix["sampling"], **kw)
    want = check.serve_gaps(decoder, params, samples, cell.dims,
                            cell.mix["sampling"], **kw)
    assert got == want
    assert got["program"]["logit_gap"] > 0


def test_training_gaps_through_the_cell_equal_the_decoders():
    cell = tiny.tiny_cell("qwen1.5-0.5b.train-4k")
    cfg = common.arch_config(cell.config, cell.reference)
    params = common.make_params(cfg, 4, cell.reference, cell.dims)
    batches = [np.asarray(b) for b, _ in zip(
        traffic.train_batches(cfg.vocab, 2, 64, 4), range(3))]
    tr = cell.config["trainer"]
    got = check.reference_steps(cell.reference, params, batches, cell.dims,
                                tr, devices=jax.devices()[:1])
    want = check.reference_steps(decoder, params, batches, cell.dims, tr)
    assert got == want


def test_a_leaf_no_rule_covers_is_an_error(tmp_path):
    d = common.load_cell("qwen1.5-0.5b.chat").dims
    with pytest.raises(common.BenchError, match="A_log"):
        decoder.leaf_rule(("blocks", "p0", "ssm", "A_log"), (1, 8), d)
    # the rule keys on the whole path: an expert's wd is not an MLP's
    with pytest.raises(common.BenchError):
        decoder.leaf_rule(("blocks", "p1", "moe", "wd"), (1, 4, 8, 8), d)
    cell = common.load_cell("toy.train", _toy_root(tmp_path))
    cfg = common.arch_config(cell.config, cell.reference)
    with pytest.raises(common.BenchError, match="no weight rule"):
        common.make_params(cfg, 1, decoder, d)


def test_no_harness_file_imports_the_decoder_by_name():
    pattern = re.compile(r"^\s*(from\s+\S*\s+)?import\s+.*\bdecoder\b|"
                         r"^\s*from\s+\S*decoder\s+import", re.M)
    for path in common.BENCH.rglob("*.py"):
        rel = path.relative_to(common.BENCH).as_posix()
        if rel == "reference/decoder.py" or rel.startswith("tests/"):
            continue
        assert not pattern.search(path.read_text()), rel


# ---------------------------------------------------------------------------
# a new architecture as new files only


def _toy_root(root: Path) -> Path:
    """A checkout of the benchmark's own files with a cell for the toy
    hybrid added as files: its reference module, configuration, mix and
    limits, and its entries in ``BENCHMARK.json``."""
    shutil.copytree(common.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(DATA / "toy_hybrid.py", root / "bench/reference/toy_hybrid.py")
    shutil.copy(DATA / "toy_hybrid.json", root / "bench/configs/toy.json")
    (root / "bench/mixes/toy-train.json").write_text(json.dumps(
        {"kind": "train", "batch_per_chip": 2, "seq": 64, "check_steps": 3}))
    (root / "bench/limits/toy.train.json").write_text(json.dumps(
        {"loss_rel_gap": 1e-3}))
    bench = common.load_json(common.ROOT / "BENCHMARK.json")
    bench["configs"].append({"name": "toy", "source": "-",
                             "file": "bench/configs/toy.json",
                             "reduced": [], "why": "-"})
    bench["workloads"].append({"name": "toy.train", "config": "toy",
                               "traffic": "toy-train", "chips": 1,
                               "why": "-"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_new_architecture_needs_only_new_files(tmp_path):
    root = _toy_root(tmp_path)
    cell = common.load_cell("toy.train", root)
    assert Path(cell.reference.__file__) == (
        root / "bench/reference/toy_hybrid.py")
    cfg = common.arch_config(cell.config, cell.reference)
    # every key the module declares is applied to what runs
    assert (cfg.n_experts, cfg.top_k, cfg.ssm_state, cfg.ssm_heads) == (
        4, 2, 32, 8)
    assert common.kv_bytes_per_token(cfg) == 1024        # one attention layer
    params = common.make_params(cfg, 11, cell.reference, cell.dims)
    flat = {common.leaf_path(p): np.asarray(v) for p, v in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    for path, x in flat.items():
        mean, std = cell.reference.leaf_rule(path, x.shape, cell.dims)
        if x.size >= 4096:
            assert x.mean() == pytest.approx(mean, abs=0.1 * std), path
            assert x.std() == pytest.approx(std, rel=0.1), path
        elif mean:
            assert abs(x.mean() - mean) < 2 * std, path
    # an expert's wd and a dense MLP's wd follow rules of their own
    assert (flat[("blocks", "p1", "moe", "wd")].std()
            == pytest.approx(0.5 * flat[("blocks", "p0", "mlp", "wd")].std(),
                             rel=0.1))
    # the counters compose the module's counts: one attention layer of 8
    d = cell.dims
    f, b = counters.paged_attn_cost(cell.reference, d, [100])
    assert f == 4.0 * 1 * d["H"] * d["hd"] * 100
    assert counters.decode_flops(cell.reference, d, 1) == (
        cell.reference.matmul_flops_per_token(d)
        + 4.0 * d["H"] * d["hd"])


def test_a_stated_count_the_program_contradicts_is_refused(tmp_path):
    root = _toy_root(tmp_path)
    cell = common.load_cell("toy.train", root)
    pool = cell.config["kv_pool"]
    for key, wrong in [("state_bytes_per_lane", pool["state_bytes_per_lane"]
                        + 4), ("bytes_per_token", 2048)]:
        conf = json.loads(json.dumps(cell.config))
        conf["kv_pool"][key] = wrong
        with pytest.raises(common.BenchError, match="bytes"):
            common.arch_config(conf, cell.reference)
    # a hybrid's slot states have to be stated
    conf = json.loads(json.dumps(cell.config))
    del conf["kv_pool"]["state_bytes_per_lane"]
    with pytest.raises(common.BenchError, match="state bytes"):
        common.arch_config(conf, cell.reference)
    # a key the module maps is applied to what runs
    conf = json.loads(json.dumps(cell.config))
    conf["model"]["num_local_experts"] = 5
    assert common.arch_config(conf, cell.reference).n_experts == 5


def test_a_configuration_without_a_reference_is_an_error(tmp_path):
    root = _toy_root(tmp_path)
    conf = json.loads((root / "bench/configs/toy.json").read_text())
    del conf["reference"]
    (root / "bench/configs/toy.json").write_text(json.dumps(conf))
    with pytest.raises(common.BenchError, match="reference"):
        common.load_cell("toy.train", root)
    conf["reference"] = "../../toy"
    (root / "bench/configs/toy.json").write_text(json.dumps(conf))
    with pytest.raises(common.BenchError, match="module name"):
        common.load_cell("toy.train", root)
