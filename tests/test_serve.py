"""Serving engines: static vs paged parity, block-allocator invariants,
continuous-batching slot recycling (ISSUE 4 / DESIGN.md §9)."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import get_model, reduced
from repro.serve import (BlockAllocator, BlockTables, PagedServeEngine,
                         PagingError, ServeEngine, SINK_BLOCK)

KEY = jax.random.PRNGKey(0)


def _setup(arch):
    cfg = reduced(get_config(arch))
    params = get_model(cfg).init(KEY)
    return cfg, params


def _prompts(cfg, lengths, seed=1):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, cfg.vocab, L)) for L in lengths]


# ---------------------------------------------------------------------------
# allocator / block tables

def test_allocator_alloc_free_cycle():
    a = BlockAllocator(num_blocks=8, block_size=4)
    assert a.num_free == 7                     # block 0 is the sink
    blocks = a.alloc(3)
    assert SINK_BLOCK not in blocks
    assert a.in_use == 3 and a.peak_in_use == 3
    a.free(blocks)
    assert a.in_use == 0 and a.num_free == 7
    assert a.peak_in_use == 3                  # high-water mark sticks


def test_allocator_double_free_raises():
    a = BlockAllocator(num_blocks=8, block_size=4)
    (b,) = a.alloc(1)
    a.free([b])
    with pytest.raises(PagingError):
        a.free([b])
    with pytest.raises(PagingError):
        a.free([SINK_BLOCK])                   # the sink is never in use


def test_allocator_exhaustion_raises():
    a = BlockAllocator(num_blocks=4, block_size=4)
    a.alloc(3)
    with pytest.raises(PagingError):
        a.alloc(1)


def test_block_tables_ensure_release():
    a = BlockAllocator(num_blocks=16, block_size=4)
    t = BlockTables(a, max_batch=2, max_pages=5)
    t.ensure(0, 9)                             # 3 pages of 4
    assert t.n_pages(0) == 3 and a.in_use == 3
    t.ensure(0, 9)                             # idempotent
    assert a.in_use == 3
    t.ensure(0, 13)                            # grow by one page
    assert t.n_pages(0) == 4 and a.in_use == 4
    assert all(b != SINK_BLOCK for b in t.row(0)[:4])
    with pytest.raises(PagingError):
        t.ensure(1, 4 * 5 + 1)                 # beyond max_pages
    t.release(0)
    assert a.in_use == 0
    assert all(b == SINK_BLOCK for b in t.row(0))


# ---------------------------------------------------------------------------
# static vs paged: identical greedy tokens on mixed-length prompts

@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "gemma2-2b",
                                  "mamba2-130m"])
def test_static_paged_parity_mixed_lengths(arch):
    """Continuous batching is a scheduling + memory-layout change; the
    sampled tokens must be bit-identical to the static engine's.  Covers
    GQA (qwen), sliding-window + softcap (gemma2) and the SSM recurrent
    state (mamba2); prompt lengths straddle block boundaries."""
    cfg, params = _setup(arch)
    prompts = _prompts(cfg, (9, 16, 5, 12))    # 16 = exact block boundary
    static = ServeEngine(cfg, params, max_len=40)
    toks, _ = static.generate(prompts, max_new_tokens=6, warmup=False)
    paged = PagedServeEngine(cfg, params, block_size=4, max_batch=3,
                             max_len=40, prefill_chunk=8)
    outs, _ = paged.generate(prompts, max_new_tokens=6, warmup=False)
    for i in range(len(prompts)):
        assert [int(t) for t in toks[i]] == outs[i], f"request {i}"


def test_paged_uneven_budgets_and_slot_reuse():
    """More requests than lanes with uneven generation budgets: every
    request completes with its own budget, freed slots are recycled, and
    the allocator ends the run empty (no leaked blocks)."""
    cfg, params = _setup("qwen1.5-0.5b")
    prompts = _prompts(cfg, (7, 3, 11, 5, 9, 4))
    budgets = [2, 7, 3, 5, 1, 4]
    eng = PagedServeEngine(cfg, params, block_size=4, max_batch=2,
                           max_len=32, prefill_chunk=8)
    outs, stats = eng.generate(prompts, max_new_tokens=budgets,
                               warmup=False)
    assert [len(o) for o in outs] == budgets
    assert eng.alloc.in_use == 0               # everything released
    assert not eng.busy
    assert stats.peak_cache_blocks > 0
    # 2 lanes of <= 4 pages: the pool high-water mark can never exceed
    # the per-lane worst case
    assert stats.peak_cache_blocks <= 2 * eng.max_pages
    # slots were actually recycled: 6 requests through 2 lanes
    assert all(r is None for r in eng.slots)


def test_paged_matches_static_with_slot_reuse():
    """Token parity must survive slot recycling: a recycled lane's pool
    blocks and SSM state rows held a previous request's data."""
    cfg, params = _setup("qwen1.5-0.5b")
    prompts = _prompts(cfg, (6, 13, 4, 10, 7), seed=3)
    static = ServeEngine(cfg, params, max_len=32)
    toks, _ = static.generate(prompts, max_new_tokens=5, warmup=False)
    paged = PagedServeEngine(cfg, params, block_size=4, max_batch=2,
                             max_len=32, prefill_chunk=16)
    outs, _ = paged.generate(prompts, max_new_tokens=5, warmup=False)
    for i in range(len(prompts)):
        assert [int(t) for t in toks[i]] == outs[i], f"request {i}"


def test_paged_rejects_overlong_and_encdec():
    """Unservable requests get a TYPED rejection (DESIGN.md §14) — no
    exception escapes add_request for an overload/shape problem."""
    from repro.serve import Status
    from repro.serve.engine import REJECT_PROMPT_TOO_LONG

    cfg, params = _setup("qwen1.5-0.5b")
    eng = PagedServeEngine(cfg, params, block_size=4, max_batch=2,
                           max_len=16)
    t = eng.add_request([1] * 15, 8)           # prompt + budget > max_len
    assert not t.accepted and t.reason == REJECT_PROMPT_TOO_LONG
    assert eng.results[t.rid].status is Status.SHED
    assert not eng.busy                        # never enqueued
    tiny = PagedServeEngine(cfg, params, block_size=4, max_batch=2,
                            max_len=16, num_blocks=3)
    t = tiny.add_request([1] * 10, 4)          # needs 4 blocks of the 2:
    assert not t.accepted                      # could never be admitted
    assert t.reason == REJECT_PROMPT_TOO_LONG
    wcfg, wparams = _setup("whisper-base")
    with pytest.raises(ValueError):            # arch limitation, not load
        PagedServeEngine(wcfg, wparams)


def test_static_engine_compile_time_reported_separately():
    """Satellite: the first static call used to fold jit compile into
    prefill_s/decode_s; with warmup the timed phases exclude it."""
    cfg, params = _setup("qwen1.5-0.5b")
    eng = ServeEngine(cfg, params, max_len=24)
    prompts = _prompts(cfg, (5, 9))
    toks, stats = eng.generate(prompts, max_new_tokens=4)
    assert toks.shape == (2, 4)
    assert stats.compile_s > 0 and stats.decode_s > 0
    # both generates run fully warm (warmup compiled everything), so the
    # first decode_s must be the same order as a repeat run — if compile
    # had leaked into the timed phase it would be ~100x larger.  Robust
    # to persistent compilation caches, unlike asserting on compile_s.
    _, again = eng.generate(prompts, max_new_tokens=4, warmup=False)
    assert stats.decode_s < 20 * again.decode_s


def test_static_mixed_length_logits_ignore_padding():
    """Satellite: tail-padded prompts must produce the same greedy tokens
    as running each prompt alone (pad id 0 is a real vocab id — only the
    per-sequence length mask keeps it out)."""
    cfg, params = _setup("qwen1.5-0.5b")
    prompts = _prompts(cfg, (5, 12), seed=7)
    eng = ServeEngine(cfg, params, max_len=24)
    toks, _ = eng.generate(prompts, max_new_tokens=4, warmup=False)
    for i, p in enumerate(prompts):
        solo, _ = eng.generate([p], max_new_tokens=4, warmup=False)
        assert list(toks[i]) == list(solo[0]), f"prompt {i}"


# ---------------------------------------------------------------------------
# train -> serve handoff (ISSUE 7 / DESIGN.md §12)

@pytest.mark.mesh
def test_trained_checkpoint_serves_identically():
    """A checkpoint written by a Trainer on a 2x2 (data, model) mesh
    loads into PagedServeEngine on a single device and produces greedy
    tokens bit-identical to serving the in-memory trained params — the
    elastic train->serve handoff."""
    from mesh_subproc import run_sub
    out = run_sub("""
    import tempfile, jax, numpy as np
    from repro.configs import get_config
    from repro.data import SyntheticLM
    from repro.models import reduced
    from repro.serve import PagedServeEngine
    from repro.train import TrainConfig, Trainer, latest_checkpoint, \
        load_checkpoint

    cfg = reduced(get_config("qwen1.5-0.5b"))
    root = tempfile.mkdtemp()
    tcfg = TrainConfig(lr=1e-2, total_steps=4, warmup_steps=1, log_every=2,
                       checkpoint_every=3, checkpoint_dir=root)
    tr = Trainer(cfg, tcfg)
    from repro.dist import make_mesh
    with jax.set_mesh(make_mesh((2, 2), ("data", "model"))):
        params, _ = tr.fit(iter(SyntheticLM(cfg.vocab, 32, 4, n_batches=4)))
    tr.wait_for_checkpoint()
    # NOTE: checkpoint lands at step 3 (the last update), so the saved
    # params ARE the in-memory ones fit() returned.
    restored, step = load_checkpoint(latest_checkpoint(root))
    assert step == 3

    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, cfg.vocab, L)) for L in (5, 9, 12)]
    def greedy(p):
        eng = PagedServeEngine(cfg, p, block_size=8, max_batch=3,
                               max_len=32)
        outs, _ = eng.generate(prompts, max_new_tokens=6)
        return [list(map(int, o)) for o in outs]

    mem = greedy(jax.device_get(params))
    ck = greedy(restored["params"])
    assert mem == ck, (mem, ck)
    print("HANDOFF_OK", mem[0][:4])
    """, devices=4)
    assert "HANDOFF_OK" in out


# ---------------------------------------------------------------------------
# quantized KV-cache serving + fused sampling (DESIGN.md §13)

# Largest logit change the int8 cache may cause on the reduced qwen config
# (per-row symmetric int8 K/V; about 0.015 is seen).  An argmax can only
# flip where the fp logits of the two tokens lie within 2x this of each
# other.
INT8_LOGIT_BOUND = 0.02


def test_paged_int8_greedy_parity_and_bytes():
    """int8 paged engine: same greedy tokens as the fp engine on a short
    workload, up to a near-tie — where a request's streams first differ,
    the fp logits of the two tokens must lie within 2 x INT8_LOGIT_BOUND
    (the streams are free to differ after that) — at the byte-model-
    predicted fraction of the fp cache (the per-row f32 scales included,
    peak block count identical)."""
    from repro.core.memplan import kv_cache_bytes_paged
    cfg, params = _setup("qwen1.5-0.5b")
    model = get_model(cfg)
    prompts = _prompts(cfg, (16, 24, 32), seed=2)
    outs, stats = {}, {}
    for kd in (None, "int8"):
        eng = PagedServeEngine(cfg, params, block_size=8, max_batch=3,
                               max_len=48, prefill_chunk=16, kv_dtype=kd)
        outs[kd], stats[kd] = eng.generate(prompts, max_new_tokens=6,
                                           warmup=False)
    matched = 0
    for prompt, fp, q in zip(prompts, outs[None], outs["int8"]):
        fp, q = list(map(int, fp)), list(map(int, q))
        j = next((i for i, (a, b) in enumerate(zip(fp, q)) if a != b), None)
        if j is None:
            matched += 1
            continue
        seq = np.asarray([prompt + fp[:j]], np.int32)
        logits, _ = model.prefill(params, {"tokens": seq})
        row = np.asarray(logits, np.float32).reshape(-1)
        assert row.argmax() == fp[j]
        assert row[fp[j]] - row[q[j]] <= 2 * INT8_LOGIT_BOUND, (j, fp, q)
    assert matched >= 2, (outs[None], outs["int8"])
    assert stats["int8"].peak_cache_blocks == stats[None].peak_cache_blocks
    # measured peak == model EXACTLY, and >= 1.8x below fp
    blocks = stats["int8"].peak_cache_blocks
    model = kv_cache_bytes_paged(cfg, [], 8, kv_dtype="int8")["block_bytes"]
    assert stats["int8"].peak_cache_bytes == blocks * model
    assert stats[None].peak_cache_bytes / stats["int8"].peak_cache_bytes \
        >= 1.8


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8_e4m3", "fp8_e5m2"])
def test_paged_cache_byte_model_is_exact(kv_dtype):
    """memplan's block_bytes == the real pool allocation (codes + scale
    tensors), leaf for leaf, for every supported storage dtype."""
    import numpy as np
    from repro.core.memplan import _DTYPE_BYTES, kv_cache_bytes_paged
    from repro.models import get_model
    cfg, _ = _setup("qwen1.5-0.5b")
    specs = get_model(cfg).paged_cache_specs(10, 8, 4, kv_dtype=kv_dtype)
    real = sum(int(np.prod(s.shape)) * _DTYPE_BYTES[str(s.dtype)]
               for s in jax.tree.leaves(specs))
    model = kv_cache_bytes_paged(cfg, [], 8, kv_dtype=kv_dtype)
    assert real == model["block_bytes"] * 10


def test_paged_engine_rejects_unknown_kv_dtype():
    cfg, params = _setup("qwen1.5-0.5b")
    with pytest.raises(ValueError, match="kv.dtype"):
        PagedServeEngine(cfg, params, max_len=32, kv_dtype="int4")


def test_paged_engine_fused_sampling_path():
    """top-k/top-p routes through the fused kernel: reproducible under a
    seed, different from greedy, tokens in-vocab."""
    cfg, params = _setup("qwen1.5-0.5b")
    prompts = _prompts(cfg, (9, 14), seed=4)

    def go(**kw):
        eng = PagedServeEngine(cfg, params, block_size=8, max_batch=2,
                               max_len=48, **kw)
        outs, _ = eng.generate(prompts, max_new_tokens=8, temperature=0.9,
                               seed=13, warmup=False)
        return [list(map(int, o)) for o in outs]

    a = go(top_k=25, top_p=0.9)
    assert a == go(top_k=25, top_p=0.9)            # seed-reproducible
    assert all(0 <= t < cfg.vocab for o in a for t in o)
    greedy_eng = PagedServeEngine(cfg, params, block_size=8, max_batch=2,
                                  max_len=48)
    g, _ = greedy_eng.generate(prompts, max_new_tokens=8, warmup=False)
    assert a != [list(map(int, o)) for o in g]


def test_static_engine_fused_sampling_path():
    cfg, params = _setup("qwen1.5-0.5b")
    prompts = _prompts(cfg, (7, 11), seed=5)
    eng = ServeEngine(cfg, params, max_len=32)
    a, _ = eng.generate(prompts, max_new_tokens=6, temperature=0.8,
                        top_k=30, top_p=0.95, seed=3, warmup=False)
    b, _ = eng.generate(prompts, max_new_tokens=6, temperature=0.8,
                        top_k=30, top_p=0.95, seed=3, warmup=False)
    assert (a == b).all()
    assert a.shape == (2, 6)
