"""Elastic restore (ISSUE 7 / DESIGN.md §12): sharded save -> restore
onto a DIFFERENT mesh is bit-exact — across dp/model/stage reshapes,
restore-to-single-device, every arch config in the partition rule table,
and a property suite over the resharding assembly math itself.  The
resume-parity gates check that training continued from a checkpoint on a
reshaped mesh tracks the uninterrupted run's losses to <= 1e-6."""
import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from mesh_subproc import run_sub

# ---------------------------------------------------------------------------
# property suite: the resharding math (save grid -> target grid), pure host


def _chunk(arr, grid):
    """Shards of ``arr`` under a per-dim chunk grid — what the device
    shards of a NamedSharding layout look like on disk: (start, block)
    pairs covering the array exactly once."""
    assert len(grid) == arr.ndim
    def splits(dim, k):
        q = dim // k
        return [(i * q, q) for i in range(k)]
    out = [((), arr)] if arr.ndim == 0 else []
    if arr.ndim == 0:
        return out
    import itertools
    per_dim = [splits(d, k) for d, k in zip(arr.shape, grid)]
    for combo in itertools.product(*per_dim):
        start = tuple(s for s, _ in combo)
        ix = tuple(slice(s, s + n) for s, n in combo)
        out.append((start, np.ascontiguousarray(arr[ix])))
    return out


def _write_fake_ckpt(tmp_path, arr, grid):
    """A manifest leaf + shard files exactly as ``save_checkpoint`` lays
    them out, but with the chunk grid chosen by the test."""
    meta = {"path": [["k", "w"]], "shape": list(arr.shape),
            "dtype": str(arr.dtype), "shards": []}
    for j, (start, block) in enumerate(_chunk(arr, grid)):
        f = tmp_path / f"l0_s{j}.bin"
        f.write_bytes(block.tobytes())
        meta["shards"].append({"file": f.name, "start": list(start),
                               "shape": list(block.shape)})
    return meta


def _divisors(n):
    return [k for k in (1, 2, 3, 4) if n % k == 0]


@pytest.mark.parametrize("shape,save_grid,target_grid", [
    ((8, 6), (2, 3), (4, 1)),          # dp-major -> model-major
    ((8, 6), (4, 1), (1, 3)),          # model-only target
    ((12,), (4,), (3,)),               # non-nested split boundaries
    ((4, 4, 8), (2, 1, 4), (1, 4, 2)), # 3-D (stacked-blocks style)
    ((8, 6), (2, 2), (1, 1)),          # restore to single device
    ((8, 6), (1, 1), (4, 3)),          # replicated save -> sharded target
])
def test_reshard_assembly_exact(tmp_path, shape, save_grid, target_grid):
    from repro.train.checkpoint import _assemble
    rng = np.random.RandomState(0)
    arr = rng.randn(*shape).astype(np.float32)
    meta = _write_fake_ckpt(tmp_path, arr, save_grid)
    for start, block in _chunk(arr, target_grid):
        ix = tuple(slice(s, s + n) for s, n in zip(start, block.shape))
        got = _assemble(tmp_path, meta, ix)
        np.testing.assert_array_equal(got, block)


@settings(max_examples=60, deadline=None)
@given(st.data())
def test_reshard_assembly_property(tmp_path_factory, data):
    """Any save grid -> any target grid reconstructs every target shard
    bit-exactly (the dp<->pp<->seq reshape space, abstractly)."""
    from repro.train.checkpoint import _assemble
    ndim = data.draw(st.integers(0, 3), label="ndim")
    shape = tuple(data.draw(st.sampled_from([1, 2, 3, 4, 6, 12]),
                            label=f"dim{i}") for i in range(ndim))
    save_grid = tuple(data.draw(st.sampled_from(_divisors(d)),
                                label=f"sg{i}") for i, d in enumerate(shape))
    tgt_grid = tuple(data.draw(st.sampled_from(_divisors(d)),
                               label=f"tg{i}") for i, d in enumerate(shape))
    tmp = tmp_path_factory.mktemp("reshard")
    arr = np.arange(int(np.prod(shape, dtype=np.int64)),
                    dtype=np.float32).reshape(shape)
    meta = _write_fake_ckpt(tmp, arr, save_grid)
    for start, block in _chunk(arr, tgt_grid):
        ix = tuple(slice(s, s + n) for s, n in zip(start, block.shape))
        np.testing.assert_array_equal(_assemble(tmp, meta, ix), block)


def test_rule_table_round_trips_through_json():
    """Every role in the partition rule table survives the manifest's
    spec serialization unchanged (the spec each leaf "was saved under")."""
    from jax.sharding import PartitionSpec as P
    from repro.dist.partition import _PARAM_RULES
    from repro.train.checkpoint import _spec_from_json, _spec_to_json
    for role, entries in _PARAM_RULES.items():
        spec = P(*entries)
        back = _spec_from_json(_spec_to_json(spec, len(entries)))
        assert tuple(back) == tuple(spec), role


# ---------------------------------------------------------------------------
# real meshes (subprocess; 4/8 forced host devices)


@pytest.mark.mesh
def test_elastic_roundtrip_all_archs():
    """Sharded save on a 2x2 (data, model) mesh -> restore onto 1x4 and
    onto a single device, bit-exact, for EVERY config in the registry
    (the rule table resolves per arch: dense GQA, MoE, SSM, hybrid,
    VLM-prefix, enc-dec)."""
    out = run_sub("""
    import tempfile, jax, numpy as np
    from repro.configs import ARCH_IDS, get_config
    from repro.models import get_model, reduced
    from repro.dist.partition import make_shardings, param_pspecs
    from repro.train import load_checkpoint, save_checkpoint

    from repro.dist import make_mesh
    mesh_a = make_mesh((2, 2), ("data", "model"))
    mesh_b = make_mesh((4,), ("model",))
    for arch in ARCH_IDS:
        cfg = reduced(get_config(arch))
        params = get_model(cfg).init(jax.random.PRNGKey(0))
        state = {"params": params}
        ref = [np.asarray(x) for x in jax.tree.leaves(jax.device_get(state))]
        sharded = jax.device_put(
            state, make_shardings(mesh_a, param_pspecs(None, state, mesh_a)))
        d = tempfile.mkdtemp()
        save_checkpoint(d, sharded, step=0)
        n_multi = sum(
            1 for leaf in jax.tree.leaves(sharded)
            if len({tuple(int(sl.start or 0) for sl in s.index)
                    for s in leaf.addressable_shards}) > 1)
        assert n_multi > 0, f"{arch}: nothing was actually sharded"
        for tag, tgt in (("1x4", mesh_b), ("single", None)):
            restored, _ = load_checkpoint(d, like=state, mesh=tgt)
            for a, b in zip(jax.tree.leaves(jax.device_get(restored)), ref):
                assert np.array_equal(np.asarray(a), b), (arch, tag)
        print(arch, "OK", n_multi, "sharded leaves")
    print("ALL_ARCHS_OK")
    """, devices=4)
    assert "ALL_ARCHS_OK" in out


@pytest.mark.mesh
def test_elastic_roundtrip_opt_state_and_stage_mesh():
    """Params + momentum opt-state saved under a PIPELINED (stage, data)
    mesh restore bit-exactly onto model-parallel, single-device, and
    back onto a different stage mesh (dp x pp 2x2 -> 1x4 and friends)."""
    out = run_sub("""
    import tempfile, jax, numpy as np
    from repro.configs import get_config
    from repro.models import get_model, reduced
    from repro.dist.partition import make_shardings, param_pspecs
    from repro.dist.pipeline import stage_pspecs
    from repro.optim import sgd_momentum
    from repro.train import load_checkpoint, save_checkpoint

    cfg = reduced(get_config("qwen1.5-0.5b"))
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    state = {"params": params, "opt": sgd_momentum().init(params)}
    ref = [np.asarray(x) for x in jax.tree.leaves(jax.device_get(state))]

    from repro.dist import make_mesh
    mesh_pp = make_mesh((2, 2), ("stage", "data"))
    sharded = jax.device_put(
        state, make_shardings(mesh_pp, stage_pspecs(None, state, mesh_pp)))
    d = tempfile.mkdtemp()
    save_checkpoint(d, sharded, step=3)

    # pipelined 2x2 -> unpipelined 1x4
    mesh_b = make_mesh((4,), ("model",))
    rb, step = load_checkpoint(d, like=state, mesh=mesh_b)
    assert step == 3
    for a, b in zip(jax.tree.leaves(jax.device_get(rb)), ref):
        assert np.array_equal(np.asarray(a), b)

    # pipelined 2x2 -> single device (template-free: the serve handoff)
    rs, _ = load_checkpoint(d)
    for a, b in zip(jax.tree.leaves(rs), ref):
        assert np.array_equal(np.asarray(a), b)

    # dp-style save -> restore INTO an ambient stage mesh (grow the run)
    mesh_dp = make_mesh((4,), ("data",))
    d2 = tempfile.mkdtemp()
    save_checkpoint(d2, jax.device_put(
        state, make_shardings(mesh_dp, param_pspecs(None, state, mesh_dp))))
    with jax.set_mesh(mesh_pp):
        rp, _ = load_checkpoint(d2, like=state)
    blk = jax.tree.leaves(rp["params"]["blocks"])[0]
    assert "stage" in str(blk.sharding.spec), blk.sharding.spec
    for a, b in zip(jax.tree.leaves(jax.device_get(rp)), ref):
        assert np.array_equal(np.asarray(a), b)
    print("ELASTIC_OPT_STAGE_OK")
    """, devices=4)
    assert "ELASTIC_OPT_STAGE_OK" in out


@pytest.mark.mesh
def test_resume_parity_across_mesh_reshapes():
    """Acceptance gate: training resumed from a sharded checkpoint onto
    a DIFFERENT mesh matches the uninterrupted run's per-step losses to
    <= 1e-6 for 5 steps, on two distinct reshape pairs:
    (2x2 data x model -> 1x4 model) and (4x1 data -> single device)."""
    out = run_sub("""
    import tempfile, itertools, jax, numpy as np
    from repro.configs import get_config
    from repro.data import SyntheticLM
    from repro.models import reduced
    from repro.train import TrainConfig, Trainer, latest_checkpoint, \
        load_checkpoint

    cfg = reduced(get_config("qwen1.5-0.5b"))
    STEPS, CKPT_AT = 11, 6

    def data():
        return iter(SyntheticLM(cfg.vocab, 32, 4, n_batches=STEPS))

    def losses(tr):
        return {h["step"]: h["loss"] for h in tr.history}

    def uninterrupted(mesh_ctx):
        tcfg = TrainConfig(lr=1e-2, total_steps=STEPS, warmup_steps=2,
                           log_every=1, grad_clip=1.0)
        tr = Trainer(cfg, tcfg)
        with mesh_ctx():
            tr.fit(data())
        return losses(tr)

    def interrupted(mesh_a_ctx, mesh_b_ctx, root):
        # same schedule horizon as the uninterrupted run; the "crash" is
        # the data stream ending after the checkpointed step
        tcfg = TrainConfig(lr=1e-2, total_steps=STEPS, warmup_steps=2,
                           log_every=1, grad_clip=1.0,
                           checkpoint_every=CKPT_AT, checkpoint_dir=root)
        tr = Trainer(cfg, tcfg)
        with mesh_a_ctx():
            tr.fit(itertools.islice(data(), CKPT_AT + 1))
        # resume on mesh B from the committed step-6 checkpoint
        tcfg2 = TrainConfig(lr=1e-2, total_steps=STEPS, warmup_steps=2,
                            log_every=1, grad_clip=1.0)
        tr2 = Trainer(cfg, tcfg2)
        with mesh_b_ctx():
            restored, step = load_checkpoint(latest_checkpoint(root))
            assert step == CKPT_AT, step
            it = data()
            for _ in range(step + 1):
                next(it)
            tr2.fit(it, state=(restored["params"], restored["opt"]),
                    start_step=step + 1)
        return losses(tr2)

    import contextlib
    from repro.dist import make_mesh
    mesh22 = lambda: jax.set_mesh(make_mesh((2, 2), ("data", "model")))
    mesh14 = lambda: jax.set_mesh(make_mesh((4,), ("model",)))
    mesh41 = lambda: jax.set_mesh(make_mesh((4,), ("data",)))
    single = contextlib.nullcontext

    for name, (ma, mb) in {"2x2->1x4": (mesh22, mesh14),
                           "4x1->single": (mesh41, single)}.items():
        base = uninterrupted(ma)
        res = interrupted(ma, mb, tempfile.mkdtemp())
        diffs = [abs(base[s] - res[s]) for s in range(CKPT_AT + 1, STEPS)]
        assert len(diffs) >= 4
        print(name, "max loss diff", max(diffs))
        assert max(diffs) <= 1e-6, (name, diffs)
    print("RESUME_PARITY_OK")
    """, devices=4)
    assert "RESUME_PARITY_OK" in out
