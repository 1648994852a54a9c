"""Pipeline parallelism (dist/pipeline.py, DESIGN.md §10): the 1F1B
schedule's bubble/byte models, stage-boundary permute correctness under
multi-stage meshes (fwd + bwd), pp x dp composition parity against pure
data parallelism, and the TrainConfig/flag validation surface.

Multi-device behaviour needs --xla_force_host_platform_device_count set
before jax initializes, so mesh tests run their bodies in a subprocess
(the ISSUE-5 acceptance harness: 4 stages, fwd+bwd).
"""
import jax
import pytest

from mesh_subproc import run_sub


# ---------------------------------------------------------------------------
# in-process: schedule math and validation (no devices needed)

def simulate_schedule(n_stages: int, microbatches: int):
    """Tick-by-tick fill–drain simulation: stage s runs microbatch t - s.

    Returns (active stage-ticks, total stage-ticks) — the oracle for
    ``pipeline_bubble_fraction``."""
    ticks = microbatches + n_stages - 1
    active = total = 0
    for t in range(ticks):
        for s in range(n_stages):
            total += 1
            if 0 <= t - s < microbatches:
                active += 1
    return active, total


def test_bubble_fraction_matches_simulated_schedule():
    from repro.dist.pipeline import pipeline_bubble_fraction
    for pp in (1, 2, 4, 8):
        for M in (1, 2, 4, 12, 32):
            active, total = simulate_schedule(pp, M)
            assert active == pp * M
            frac = pipeline_bubble_fraction(pp, M)
            assert abs(frac - (1 - active / total)) < 1e-12, (pp, M)


def test_permute_byte_model():
    from repro.dist.pipeline import pipeline_permute_bytes
    m = pipeline_permute_bytes(2, 64, 128, n_stages=4, microbatches=8,
                               itemsize=2)
    # fwd: M + pp - 2 = 10 hops of one (b, S, D) microbatch activation
    assert m["fwd_permutes"] == 10
    assert m["fwd_total"] == 10 * 2 * 64 * 128 * 2
    # reverse schedule permutes the activation cotangent the same count
    assert m["grad_total"] == 2 * m["fwd_total"]
    one = pipeline_permute_bytes(2, 64, 128, n_stages=1, microbatches=8)
    assert one["fwd_total"] == one["grad_total"] == 0


def test_trainconfig_validation_errors():
    """Indivisible layer / microbatch counts and seq_shard composition are
    refused with clear errors at Trainer construction."""
    from repro.configs import get_config
    from repro.models import reduced
    from repro.perf_flags import reset_flags, set_flags
    from repro.train import TrainConfig, Trainer
    cfg = reduced(get_config("qwen1.5-0.5b"))     # n_super == 2
    try:
        with pytest.raises(ValueError, match="stage groups"):
            Trainer(cfg, TrainConfig(pp_stages=3, microbatches=4))
        with pytest.raises(ValueError, match="microbatches"):
            Trainer(cfg, TrainConfig(pp_stages=2, microbatches=0))
        with pytest.raises(ValueError, match="pp_stages"):
            Trainer(cfg, TrainConfig(pp_stages=0, microbatches=2))
        set_flags(seq_shard=True)
        with pytest.raises(ValueError, match="seq_shard"):
            Trainer(cfg, TrainConfig(pp_stages=2, microbatches=4))
    finally:
        reset_flags()
    # a valid config installs the flags for the model path
    from repro.perf_flags import FLAGS
    try:
        Trainer(cfg, TrainConfig(pp_stages=2, microbatches=4))
        assert (FLAGS.pp_stages, FLAGS.microbatches) == (2, 4)
    finally:
        reset_flags()


def test_batch_divisibility_refused():
    from repro.dist.pipeline import pipeline_stack, validate_pipeline
    import jax.numpy as jnp
    w = jnp.zeros((2, 4, 4))
    x = jnp.zeros((6, 3, 4))
    with pytest.raises(ValueError, match="not divisible"):
        pipeline_stack(lambda p, h: (h, {}), w, x, microbatches=4)
    # a per-microbatch batch the data axes do not divide must be refused:
    # inside the fully-manual stage region a dropped data axis would
    # silently scale block grads by n_data (DESIGN.md §10)
    with pytest.raises(ValueError, match="data-axis"):
        validate_pipeline(n_stages=2, microbatches=8, batch=32, n_data=8)
    validate_pipeline(n_stages=2, microbatches=4, batch=32, n_data=8)


def test_stage_pspecs_and_worker_axes():
    """Blocks leaves get the stage axis on their scan dim; gradient-sync
    worker axes never include "stage" (buckets reduce over data/pod only
    — DESIGN.md §10)."""
    from jax.sharding import PartitionSpec as P
    from repro.dist import make_mesh, worker_axes
    from repro.dist.pipeline import stage_pspecs
    mesh = make_mesh((1, 1), ("stage", "data"))
    params = {"embed": jax.ShapeDtypeStruct((512, 64), "float32"),
              "blocks": {"p0": {"wq": jax.ShapeDtypeStruct((4, 64, 8, 16),
                                                           "float32")}}}
    specs = stage_pspecs(None, params, mesh)
    assert specs["blocks"]["p0"]["wq"][0] == "stage"
    assert specs["embed"] == P(None, "data")      # vocab % 1 == 0 -> kept
    assert worker_axes(mesh) == ("data",)


def test_trainer_overlap_composes_with_pipeline_fallback():
    """overlap=True under pp taps only the non-block params (block grads
    are stage-sharded; DESIGN.md §10) — one fit step must run and train
    on the sequential no-mesh fallback."""
    from repro.configs import get_config
    from repro.data import SyntheticLM
    from repro.models import reduced
    from repro.perf_flags import reset_flags
    from repro.train import TrainConfig, Trainer
    cfg = reduced(get_config("qwen1.5-0.5b"), vocab=64, d_model=64,
                  d_ff=128, n_heads=2, head_dim=32)
    try:
        tr = Trainer(cfg, TrainConfig(total_steps=2, overlap=True,
                                      pp_stages=2, microbatches=2,
                                      log_every=1))
        data = SyntheticLM(cfg.vocab, 16, 4, n_batches=2)
        tr.fit(iter(data))
        assert len(tr.history) == 2
        assert all(m["loss"] == m["loss"] for m in tr.history)  # no NaN
    finally:
        reset_flags()


def test_pipeline_rejects_enc_dec():
    from repro.configs import get_config
    from repro.models import get_model, reduced
    from repro.perf_flags import reset_flags, set_flags
    cfg = reduced(get_config("whisper-base"))
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = m.make_batch(jax.random.PRNGKey(1), "train", 2, 32)
    set_flags(pp_stages=2, microbatches=2)
    try:
        with pytest.raises(ValueError, match="enc-dec"):
            m.loss(params, batch)
    finally:
        reset_flags()


# ---------------------------------------------------------------------------
# mesh subprocess tests (>= 4 stages; ISSUE-5 acceptance harness)

@pytest.mark.mesh
def test_pipeline_stack_4_stages_fwd_bwd():
    """Stage-boundary permute correctness: a 4-stage pipeline of a toy
    stacked layer matches the sequential no-mesh oracle, forward and
    backward (params, input grads, aux)."""
    out = run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist.pipeline import pipeline_stack

    def stage_fn(w, x):
        def body(carry, wi):
            x, lb = carry
            return (jnp.tanh(x @ wi),
                    lb + jnp.sum(wi ** 2).astype(jnp.float32)), None
        (x, lb), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), w)
        return x, {"lb": lb}

    B, S, D, NS, M = 8, 16, 32, 4, 4
    w = jax.random.normal(jax.random.PRNGKey(0), (NS, D, D)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D))
    dyw = jax.random.normal(jax.random.PRNGKey(2), (B, S, D))

    y0, aux0 = pipeline_stack(stage_fn, w, x, microbatches=M)
    def loss(w, x):
        y, aux = pipeline_stack(stage_fn, w, x, microbatches=M)
        return (y * dyw).sum() + 0.5 * aux["lb"]
    g0w, g0x = jax.grad(loss, argnums=(0, 1))(w, x)

    from repro.dist import make_mesh
    mesh = make_mesh((4,), ("stage",))
    with jax.set_mesh(mesh):
        y1, aux1 = jax.jit(
            lambda w, x: pipeline_stack(stage_fn, w, x, microbatches=M)
        )(w, x)
        g1w, g1x = jax.jit(jax.grad(loss, argnums=(0, 1)))(w, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(aux1["lb"]), float(aux0["lb"]),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1w), np.asarray(g0w),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g1x), np.asarray(g0x),
                               rtol=1e-4, atol=1e-5)
    # a stack the 4-way stage axis does not divide must be refused
    bad = jax.random.normal(jax.random.PRNGKey(3), (6, D, D))
    with jax.set_mesh(mesh):
        try:
            pipeline_stack(stage_fn, bad, x, microbatches=M)
        except ValueError as e:
            assert "stage groups" in str(e), e
            print("DIVISIBILITY_OK")
    print("PIPE_MESH_OK")
    """, devices=4)
    assert "PIPE_MESH_OK" in out
    assert "DIVISIBILITY_OK" in out


@pytest.mark.mesh
def test_pipeline_moe_arch_runs_on_stage_mesh():
    """MoE under pp: the grouped-dispatch shard_map must degrade to its
    local body inside the fully-manual stage region (the batch axes are
    already per-device there) — loss and grads run and stay finite.
    Exact MoE parity is not expected: capacity is per microbatch."""
    out = run_sub("""
    import jax, numpy as np
    from repro.configs import get_config
    from repro.models import get_model, reduced
    from repro.perf_flags import reset_flags, set_flags

    cfg = reduced(get_config("dbrx-132b"))        # MoE, n_super == 2
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = m.make_batch(jax.random.PRNGKey(1), "train", 4, 32)
    loss_fn = lambda p: m.loss(p, batch)[0]
    loss0 = float(loss_fn(params))

    from repro.dist import make_mesh
    mesh = make_mesh((2, 2), ("stage", "data"))
    set_flags(pp_stages=2, microbatches=2)
    try:
        with jax.set_mesh(mesh):
            loss1 = float(jax.jit(loss_fn)(params))
            g1 = jax.jit(jax.grad(loss_fn))(params)
    finally:
        reset_flags()
    assert np.isfinite(loss1), loss1
    # CE dominates and is batch-separable; only the aux terms may drift
    assert abs(loss1 - loss0) < 0.1, (loss0, loss1)
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(g1))
    print("PP_MOE_OK")
    """, devices=4)
    assert "PP_MOE_OK" in out


@pytest.mark.mesh
def test_pipeline_model_pp_x_dp_matches_data_parallel():
    """Composition (ISSUE-5): a reduced dense model trained on a
    (2, 2) stage x data mesh (pp=2, M=2) produces the same loss and
    parameter grads as pure 1x4 data parallelism and as the no-mesh
    baseline."""
    out = run_sub("""
    import jax, numpy as np
    from repro.configs import get_config
    from repro.models import get_model, reduced
    from repro.perf_flags import reset_flags, set_flags

    cfg = reduced(get_config("qwen1.5-0.5b"))     # n_super == 2
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = m.make_batch(jax.random.PRNGKey(1), "train", 4, 32)
    loss_fn = lambda p: m.loss(p, batch)[0]
    loss0 = float(loss_fn(params))
    g0 = jax.tree.leaves(jax.grad(loss_fn)(params))

    # pure data parallelism (1 x 4)
    from repro.dist import make_mesh
    mesh_dp = make_mesh((4,), ("data",))
    with jax.set_mesh(mesh_dp):
        loss_dp = float(jax.jit(loss_fn)(params))
        g_dp = jax.tree.leaves(jax.jit(jax.grad(loss_fn))(params))

    # pipeline x data (2 x 2)
    mesh_pp = make_mesh((2, 2), ("stage", "data"))
    set_flags(pp_stages=2, microbatches=2)
    try:
        with jax.set_mesh(mesh_pp):
            loss_pp = float(jax.jit(loss_fn)(params))
            g_pp = jax.tree.leaves(jax.jit(jax.grad(loss_fn))(params))
    finally:
        reset_flags()

    for name, l, g in (("dp", loss_dp, g_dp), ("pp", loss_pp, g_pp)):
        assert abs(l - loss0) < 1e-5, (name, l, loss0)
        mx = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
                 for a, b in zip(g0, g))
        assert mx < 1e-5, (name, mx)
        print(name, "maxdiff", mx)
    mx = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
             for a, b in zip(g_dp, g_pp))
    assert mx < 1e-5, mx
    print("PP_X_DP_OK")
    """, devices=4)
    assert "PP_X_DP_OK" in out
