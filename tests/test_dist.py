"""Distribution layer: sharding rules, hierarchical collectives, dry-run.

Multi-device behaviour needs --xla_force_host_platform_device_count, which
must be set before jax initializes — these tests run their bodies in a
subprocess.
"""
import pytest

from mesh_subproc import run_sub


@pytest.mark.mesh
def test_hierarchical_allreduce_matches_flat():
    out = run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist import gradient_sync
    from repro.dist import make_mesh
    mesh = make_mesh((2, 4, 2), ("pod", "data", "model"))
    W = 8  # pod*data workers
    rng = np.random.RandomState(0)
    grads = {"a": jnp.asarray(rng.randn(W, 3, 5), jnp.float32),
             "b": jnp.asarray(rng.randn(W, 7), jnp.float32)}
    with jax.set_mesh(mesh):
        h = gradient_sync(mesh, grads, mode="hierarchical")
        f = gradient_sync(mesh, grads, mode="flat")
    for k in grads:
        want = np.asarray(grads[k]).sum(0)
        np.testing.assert_allclose(np.asarray(h[k]), want, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(f[k]), want, rtol=1e-5)
    print("SYNC_OK")
    """)
    assert "SYNC_OK" in out


@pytest.mark.mesh
def test_hierarchical_reduces_interpod_bytes():
    """The two-level schedule must move fewer bytes across 'pod' than the
    flat all-reduce (the §3.3 claim, on-mesh)."""
    out = run_sub("""
    import jax, jax.numpy as jnp, re
    from repro.dist.collectives import gradient_sync
    from repro.dist import make_mesh
    mesh = make_mesh((2, 4, 2), ("pod", "data", "model"))
    W = 8
    g = {"w": jnp.zeros((W, 4096), jnp.float32)}
    with jax.set_mesh(mesh):
        texts = {}
        for mode in ("hierarchical", "flat"):
            lowered = jax.jit(
                lambda x, mode=mode: gradient_sync(mesh, x, mode=mode)
            ).lower(g)
            texts[mode] = lowered.compile().as_text()
    def pod_coll_bytes(txt):
        # pod-axis collectives have replica groups spanning across pods:
        # count all-reduce result bytes where group contains stride >= 8
        total = 0
        for m in re.finditer(r"f32\\[(\\d+)\\][^\\n]*all-reduce", txt):
            total += int(m.group(1)) * 4
        return total
    h, f = pod_coll_bytes(texts["hierarchical"]), pod_coll_bytes(texts["flat"])
    print("H", h, "F", f)
    assert h < f, (h, f)
    print("BYTES_OK")
    """)
    assert "BYTES_OK" in out


@pytest.mark.mesh
def test_param_pspecs_cover_tree_and_divide():
    out = run_sub("""
    import jax
    from repro.configs import get_config
    from repro.dist import param_pspecs
    from repro.models import get_model
    from repro.dist import make_mesh
    mesh = make_mesh((4, 4), ("data", "model"))
    for arch in ("dbrx-132b", "mamba2-130m", "gemma2-2b", "whisper-base"):
        cfg = get_config(arch)
        specs = param_pspecs(cfg, get_model(cfg).param_specs(), mesh)
        leaves, specl = (jax.tree.leaves(get_model(cfg).param_specs()),
                         jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, '_normalized_spec_for_aval')))
        import jax.sharding as shd
        specl = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, shd.PartitionSpec))
        assert len(leaves) == len(specl), (arch, len(leaves), len(specl))
        for leaf, spec in zip(leaves, specl):
            for i, s in enumerate(spec):
                if s is None: continue
                group = (s,) if isinstance(s, str) else s
                n = 1
                for a in group: n *= mesh.shape[a]
                assert leaf.shape[i] % n == 0, (arch, leaf.shape, spec)
    print("SPECS_OK")
    """)
    assert "SPECS_OK" in out


@pytest.mark.mesh
def test_dryrun_single_pair_tiny():
    """The dry-run path end-to-end on a reduced arch (16 fake devices)."""
    out = run_sub("""
    import jax
    from repro.launch.dryrun import collective_bytes, lower_and_compile
    from repro.configs import get_config
    from dataclasses import replace
    from repro.models import reduced
    cfg = reduced(get_config("qwen1.5-0.5b"))
    from repro.dist import make_mesh
    mesh = make_mesh((4, 4), ("data", "model"))
    import repro.models.common as C
    import repro.launch.steps as S
    # shrink the input shape table for the test
    S.INPUT_SHAPES = dict(S.INPUT_SHAPES)
    S.INPUT_SHAPES["train_4k"] = C.InputShape("train_4k", 64, 8, "train")
    lowered, compiled, tl, tc = lower_and_compile(cfg, "train_4k", mesh)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes > 0
    ca = compiled.cost_analysis()
    assert ca["flops"] > 0
    coll = collective_bytes(compiled.as_text())
    assert coll["total"] > 0, coll
    print("DRYRUN_OK")
    """)
    assert "DRYRUN_OK" in out


@pytest.mark.mesh
def test_decode_step_lowering_tiny():
    out = run_sub("""
    import jax
    from repro.launch.dryrun import lower_and_compile
    from repro.configs import get_config
    from repro.models import reduced
    import repro.models.common as C
    import repro.launch.steps as S
    cfg = reduced(get_config("gemma2-2b"))
    from repro.dist import make_mesh
    mesh = make_mesh((4, 4), ("data", "model"))
    S.INPUT_SHAPES = dict(S.INPUT_SHAPES)
    S.INPUT_SHAPES["decode_32k"] = C.InputShape("decode_32k", 256, 8, "decode")
    lowered, compiled, tl, tc = lower_and_compile(cfg, "decode_32k", mesh)
    assert compiled.cost_analysis()["flops"] > 0
    print("DECODE_OK")
    """)
    assert "DECODE_OK" in out
