"""Ring attention (dist/ring.py, DESIGN.md §8): numeric parity with the
unsharded reference on multi-shard meshes, forward and backward, for
full-causal and sliding-window layers — plus the seq-shard plumbing
(batch_pspecs kind="seq", PerfFlags, long-context config gating).

Multi-device behaviour needs --xla_force_host_platform_device_count set
before jax initializes, so mesh tests run their bodies in a subprocess
(the ISSUE-3 acceptance harness: >= 4 sequence shards).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mesh_subproc import run_sub


# ---------------------------------------------------------------------------
# in-process: the no-mesh fallback is the oracle the mesh tests trust

def test_ring_no_mesh_matches_ref_fwd_bwd():
    from repro.dist.ring import ring_attention
    from repro.kernels import ref
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    B, S, H, K, hd = 2, 96, 4, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    w = jax.random.normal(ks[3], (B, S, H, hd))
    for kw in (dict(causal=True), dict(causal=True, window=24),
               dict(causal=True, window=24, softcap=10.0)):
        out = ring_attention(q, k, v, **kw)
        want = ref.flash_attention_ref(q, k, v, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        g = jax.grad(lambda *a: (ring_attention(*a, **kw) * w).sum(),
                     argnums=(0, 1, 2))(q, k, v)
        gw = jax.grad(lambda *a: (ref.flash_attention_ref(*a, **kw)
                                  * w).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gw):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


def test_ring_rejects_cross_lengths():
    from repro.dist.ring import ring_attention
    q = jnp.zeros((1, 8, 2, 4))
    kv = jnp.zeros((1, 6, 2, 4))
    with pytest.raises(ValueError, match="self-attention"):
        ring_attention(q, kv, kv)


def test_contributing_steps_and_byte_model():
    from repro.dist.ring import contributing_steps, ring_permute_bytes
    # full causal: every forward step contributes, backward wraps
    assert contributing_steps(4, 32, causal=True, window=None) == [0, 1, 2, 3]
    assert contributing_steps(4, 32, causal=True, window=33) == [0, 1]
    assert contributing_steps(4, 32, causal=True, window=33,
                              direction="bwd") == [0, 3]
    m = ring_permute_bytes(1, 128, 2, 16, 4, itemsize=2, causal=True)
    # fwd: 3 rotations x 2 tensors x (1*32*2*16*2) bytes
    assert m["fwd_total"] == 3 * 2 * (32 * 2 * 16 * 2)
    # bwd: k/v for P-1 hops, f32 dk/dv for P hops
    assert m["bwd_total"] == 3 * 2 * (32 * 2 * 16 * 2) + 4 * 2 * (32 * 2 * 16 * 4)
    assert m["grad_total"] == m["fwd_total"] + m["bwd_total"]
    one = ring_permute_bytes(1, 128, 2, 16, 1)
    assert one["fwd_total"] == one["grad_total"] == 0


def test_long_context_config_gating():
    from repro.configs import get_config
    # sub-quadratic archs keep their native variant
    cfg = get_config("gemma2-2b", long_context=True)
    assert all(s.window is not None for s in cfg.pattern)
    # full-attention archs need the ring acknowledgement
    with pytest.raises(ValueError, match="ring"):
        get_config("qwen1.5-0.5b", long_context=True)
    cfg = get_config("qwen1.5-0.5b", long_context=True, seq_shard=True)
    assert any(s.window is None for s in cfg.pattern)  # attention stays full


def test_long_500k_prefill_shape_registered():
    from repro.models import INPUT_SHAPES
    shp = INPUT_SHAPES["long_500k_prefill"]
    assert (shp.seq_len, shp.global_batch, shp.kind) == (524_288, 1,
                                                         "prefill")


# ---------------------------------------------------------------------------
# mesh subprocess tests (>= 4 sequence shards)

@pytest.mark.mesh
def test_ring_matches_ref_4_shards_fwd_bwd():
    """ISSUE-3 acceptance: ring fwd+bwd == unsharded ref on a 4-shard
    mesh, full-causal and sliding-window (window crosses chunk bounds)."""
    out = run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist.ring import ring_attention
    from repro.kernels import ref
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    B, S, H, K, hd = 2, 256, 4, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    w = jax.random.normal(ks[3], (B, S, H, hd))
    from repro.dist import make_mesh
    mesh = make_mesh((4,), ("model",))
    for kw in (dict(causal=True), dict(causal=True, window=48),
               dict(causal=True, window=100, softcap=15.0)):
        want = ref.flash_attention_ref(q, k, v, **kw)
        gw = jax.grad(lambda *a: (ref.flash_attention_ref(*a, **kw)
                                  * w).sum(), argnums=(0, 1, 2))(q, k, v)
        with jax.set_mesh(mesh):
            out = jax.jit(lambda *a: ring_attention(*a, **kw))(q, k, v)
            g = jax.jit(jax.grad(
                lambda *a: (ring_attention(*a, **kw) * w).sum(),
                argnums=(0, 1, 2)))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        for a, b in zip(g, gw):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)
        print("OK", sorted(kw))
    # a sequence the 4-way ring axis does not divide must be refused
    # with a clear error, not an opaque shard_map failure
    bad = jax.random.normal(ks[0], (B, 250, H, hd))
    bkv = jax.random.normal(ks[1], (B, 250, K, hd))
    with jax.set_mesh(mesh):
        try:
            ring_attention(bad, bkv, bkv)
        except ValueError as e:
            assert "divisible" in str(e), e
            print("DIVISIBILITY_OK")
    print("RING_MESH_OK")
    """, devices=4)
    assert "RING_MESH_OK" in out
    assert "DIVISIBILITY_OK" in out


@pytest.mark.mesh
def test_ring_pallas_inner_4_shards():
    """The flash kernel (carry mode) as the per-ring-step inner kernel,
    interpret mode, under shard_map + custom_vjp."""
    out = run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist.ring import ring_attention
    from repro.kernels import ref
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    B, S, H, K, hd = 1, 128, 4, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    w = jax.random.normal(ks[3], (B, S, H, hd))
    from repro.dist import make_mesh
    mesh = make_mesh((4,), ("model",))
    for kw in (dict(causal=True), dict(causal=True, window=40)):
        want = ref.flash_attention_ref(q, k, v, **kw)
        with jax.set_mesh(mesh):
            out = jax.jit(lambda *a: ring_attention(
                *a, inner="pallas", block_q=32, block_k=32, **kw))(q, k, v)
            g = jax.jit(jax.grad(lambda *a: (ring_attention(
                *a, inner="pallas", block_q=32, block_k=32, **kw)
                * w).sum(), argnums=(0, 1, 2)))(q, k, v)
        gw = jax.grad(lambda *a: (ref.flash_attention_ref(*a, **kw)
                                  * w).sum(), argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        for a, b in zip(g, gw):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)
    print("RING_PALLAS_OK")
    """, devices=4)
    assert "RING_PALLAS_OK" in out


@pytest.mark.mesh
def test_seq_shard_model_loss_and_grads_match():
    """PerfFlags.seq_shard + attn_impl=auto: a reduced dense model's train
    loss and parameter gradients on a (1, 4) mesh equal the no-mesh
    baseline (the ring path is numerically transparent end to end)."""
    out = run_sub("""
    import jax, numpy as np
    from repro.configs import get_config
    from repro.models import get_model, reduced
    from repro.perf_flags import reset_flags, set_flags
    cfg = reduced(get_config("qwen1.5-0.5b"))
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = m.make_batch(jax.random.PRNGKey(1), "train", 2, 64)
    loss0, _ = m.loss(params, batch)
    g0 = jax.grad(lambda p: m.loss(p, batch)[0])(params)
    from repro.dist import make_mesh
    mesh = make_mesh((1, 4), ("data", "model"))
    set_flags(seq_shard=True, attn_impl="auto")
    try:
        with jax.set_mesh(mesh):
            loss1, _ = jax.jit(m.loss)(params, batch)
            g1 = jax.jit(jax.grad(lambda p: m.loss(p, batch)[0]))(params)
    finally:
        reset_flags()
    np.testing.assert_allclose(float(loss0), float(loss1), rtol=1e-5)
    f0, f1 = jax.tree.leaves(g0), jax.tree.leaves(g1)
    assert len(f0) == len(f1)
    for a, b in zip(f0, f1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)
    print("SEQ_SHARD_MODEL_OK")
    """, devices=4)
    assert "SEQ_SHARD_MODEL_OK" in out


@pytest.mark.mesh
def test_ring_hlo_permute_bytes_match_analytic():
    """The analytic permute-byte model equals the compiled HLO exactly
    (fwd and grad), including the windowed early-stop."""
    out = run_sub("""
    import jax, jax.numpy as jnp
    from repro.dist.ring import ring_attention, ring_permute_bytes
    from repro.launch.dryrun import collective_bytes
    B, S, H, K, hd = 2, 256, 4, 2, 32
    q = jnp.zeros((B, S, H, hd), jnp.float32)
    k = jnp.zeros((B, S, K, hd), jnp.float32)
    v = jnp.zeros((B, S, K, hd), jnp.float32)
    from repro.dist import make_mesh
    mesh = make_mesh((4,), ("model",))
    for window in (None, 48):
        model = ring_permute_bytes(B, S, K, hd, 4, itemsize=4,
                                   causal=True, window=window)
        with jax.set_mesh(mesh):
            f = jax.jit(lambda *a: ring_attention(
                *a, causal=True, window=window))
            g = jax.jit(jax.grad(lambda *a: ring_attention(
                *a, causal=True, window=window).sum(), argnums=(0, 1, 2)))
            cf = collective_bytes(f.lower(q, k, v).compile().as_text())
            cg = collective_bytes(g.lower(q, k, v).compile().as_text())
        assert cf["raw"]["collective-permute"] == model["fwd_total"], (
            window, cf["raw"], model)
        assert cg["raw"]["collective-permute"] == model["grad_total"], (
            window, cg["raw"], model)
    print("RING_BYTES_OK")
    """, devices=4)
    assert "RING_BYTES_OK" in out


@pytest.mark.mesh
def test_batch_pspecs_seq_kind():
    out = run_sub("""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.dist import batch_pspecs
    from repro.dist import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    batch = {"tokens": jax.ShapeDtypeStruct((8, 64), "int32"),
             "patches": jax.ShapeDtypeStruct((8, 64, 32), "float32"),
             "scalar": jax.ShapeDtypeStruct((), "int32")}
    specs = batch_pspecs(None, batch, mesh, kind="seq")
    assert specs["tokens"] == P("data", "model"), specs["tokens"]
    assert specs["patches"] == P("data", "model", None), specs["patches"]
    assert specs["scalar"] == P()
    # non-dividing model axis on dim 1 is dropped, not an error
    odd = {"tokens": jax.ShapeDtypeStruct((8, 63), "int32")}
    assert batch_pspecs(None, odd, mesh, kind="seq")["tokens"] == \
        P("data", None)
    # other kinds unchanged
    specs = batch_pspecs(None, batch, mesh, kind="train")
    assert specs["tokens"] == P("data", None)
    print("SEQ_PSPECS_OK")
    """, devices=8)
    assert "SEQ_PSPECS_OK" in out
