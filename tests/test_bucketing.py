"""Bucketed gradient sync (DESIGN.md §7): BucketPlan packing invariants,
pack/unpack round-trip, numeric equivalence of ``mode="bucketed"`` with
``mode="flat"`` on the 2x4x2 dry-run mesh, overlap taps, and the per-key
KVStore byte attribution the bucketed cross-validation relies on.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from mesh_subproc import run_sub
from repro.dist import gradient_sync, make_mesh
from repro.dist.bucketing import BucketPlan, leaf_nbytes, overlap_taps


def _structs(shapes, dtype="float32"):
    return [jax.ShapeDtypeStruct(tuple(s), dtype) for s in shapes]


# ---------------------------------------------------------------------------
# BucketPlan invariants

def _check_invariants(plan, leaves, cap, lead_dims=0):
    # every leaf exactly once
    seen = [i for b in plan.buckets for i in b.indices]
    assert sorted(seen) == list(range(len(leaves)))
    assert len(plan.assignment()) == len(leaves)
    for b, bucket in enumerate(plan.buckets):
        # dtype-pure buckets
        assert all(str(jnp.dtype(leaves[i].dtype)) == bucket.dtype
                   for i in bucket.indices)
        # byte cap respected except single oversized leaves
        if bucket.nbytes > cap:
            assert len(bucket.indices) == 1, (b, bucket)
        # recorded sizes consistent with the leaves
        elems = [math.prod(tuple(leaves[i].shape)[lead_dims:])
                 for i in bucket.indices]
        assert list(bucket.elems) == elems
        assert bucket.nbytes == sum(elems) * jnp.dtype(bucket.dtype).itemsize


def test_plan_basic_first_fit():
    leaves = _structs([(256, 256), (1024,), (512, 512)])  # 256K, 4K, 1M
    plan = BucketPlan.build(leaves, cap_bytes=300 * 1024)
    _check_invariants(plan, leaves, 300 * 1024)
    assert plan.n_buckets == 2
    assert plan.assignment() == (0, 0, 1)  # 4K first-fits beside 256K


def test_plan_oversized_leaf_is_isolated():
    leaves = _structs([(512, 512), (8,), (8,)])  # 1M then two tiny
    plan = BucketPlan.build(leaves, cap_bytes=1024)
    _check_invariants(plan, leaves, 1024)
    # the tiny leaves must NOT ride along in the oversized bucket
    assert plan.assignment()[0] != plan.assignment()[1]
    assert plan.assignment()[1] == plan.assignment()[2]


def test_plan_mixed_dtypes_never_share_buckets():
    leaves = (_structs([(16,)], "float32") + _structs([(16,)], "bfloat16")
              + _structs([(16,)], "float32"))
    plan = BucketPlan.build(leaves, cap_bytes=1 << 20)
    _check_invariants(plan, leaves, 1 << 20)
    a = plan.assignment()
    assert a[0] == a[2] != a[1]


def test_plan_rejects_bad_cap():
    with pytest.raises(ValueError):
        BucketPlan.build(_structs([(4,)]), cap_bytes=0)


@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(min_value=1, max_value=5000), min_size=1,
                max_size=40),
       st.integers(min_value=1, max_value=16 * 1024))
def test_plan_property_partition_and_cap(sizes, cap):
    """Property test: any leaf list is partitioned exactly once and every
    multi-leaf bucket respects the byte cap."""
    leaves = _structs([(n,) for n in sizes])
    plan = BucketPlan.build(leaves, cap_bytes=cap)
    _check_invariants(plan, leaves, cap)


def test_leaf_nbytes():
    assert leaf_nbytes(jax.ShapeDtypeStruct((3, 4), "float32")) == 48
    assert leaf_nbytes(jax.ShapeDtypeStruct((), "bfloat16")) == 2


# ---------------------------------------------------------------------------
# pack / unpack round-trip

def test_pack_unpack_roundtrip_lead_dim():
    rng = np.random.RandomState(0)
    leaves = [jnp.asarray(rng.randn(4, 3, 5), np.float32),
              jnp.asarray(rng.randn(4, 7), np.float32),
              jnp.asarray(rng.randn(4, 2, 2, 2), np.float32)]
    plan = BucketPlan.build(leaves, cap_bytes=10 * 4, lead_dims=1)
    buffers = plan.pack(leaves, lead_dims=1)
    assert all(b.shape[0] == 4 for b in buffers)
    back = plan.unpack(buffers, leaves, lead_dims=1)
    for a, b in zip(leaves, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_unpack_after_lead_reduction():
    """unpack() also restores shapes when the buffers lost the lead dim
    (the gradient_sync case: sync reduces over workers)."""
    leaves = [jnp.ones((4, 3)), jnp.ones((4, 5))]
    plan = BucketPlan.build(leaves, cap_bytes=1 << 20, lead_dims=1)
    buffers = [b.sum(0) for b in plan.pack(leaves, lead_dims=1)]
    back = plan.unpack(buffers, leaves, lead_dims=1)
    assert [tuple(b.shape) for b in back] == [(3,), (5,)]
    np.testing.assert_array_equal(np.asarray(back[0]), np.full((3,), 4.0))


# ---------------------------------------------------------------------------
# gradient_sync mode="bucketed" — numerics on the 2x4x2 dry-run mesh

@pytest.mark.mesh
def test_bucketed_sync_matches_flat_on_mesh():
    out = run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist import gradient_sync
    from repro.dist import make_mesh
    mesh = make_mesh((2, 4, 2), ("pod", "data", "model"))
    W = 8
    rng = np.random.RandomState(0)
    grads = {"a": jnp.asarray(rng.randn(W, 3, 5), jnp.float32),
             "b": jnp.asarray(rng.randn(W, 7), jnp.float32),
             "c": jnp.asarray(rng.randn(W, 64), jnp.float32)}
    with jax.set_mesh(mesh):
        # tiny cap -> multiple buckets; must equal the flat reduction
        b = gradient_sync(mesh, grads, mode="bucketed", bucket_bytes=64)
        f = gradient_sync(mesh, grads, mode="flat")
    for k in grads:
        np.testing.assert_allclose(np.asarray(b[k]), np.asarray(f[k]),
                                   rtol=1e-5)
        assert b[k].shape == grads[k].shape[1:]
    print("BUCKETED_OK")
    """)
    assert "BUCKETED_OK" in out


def test_bucketed_sync_no_mesh_fallback():
    mesh = make_mesh((1,), ("data",))
    grads = {"w": jnp.arange(12, dtype=jnp.float32).reshape(4, 3)}
    out = gradient_sync(mesh, grads, mode="bucketed")
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(grads["w"]).sum(0))


# ---------------------------------------------------------------------------
# overlap taps: identity forward, identity gradients

def test_overlap_taps_identity_and_grads():
    rng = np.random.RandomState(0)
    params = {"w1": jnp.asarray(rng.randn(8, 4), np.float32),
              "w2": jnp.asarray(rng.randn(4,), np.float32)}
    x = jnp.asarray(rng.randn(3, 8), np.float32)

    def loss(p, tap):
        q = overlap_taps(p, cap_bytes=16) if tap else p
        return jnp.sum((x @ q["w1"] + q["w2"]) ** 2)

    l0, g0 = jax.value_and_grad(lambda p: loss(p, False))(params)
    l1, g1 = jax.value_and_grad(lambda p: loss(p, True))(params)
    assert float(l0) == float(l1)
    for k in params:
        np.testing.assert_array_equal(np.asarray(g0[k]), np.asarray(g1[k]))


def test_trainer_overlap_step_matches_plain():
    """A Trainer step with overlap=True is numerically identical to the
    default step (the taps only restructure the collective schedule)."""
    from repro.configs import get_config
    from repro.models import reduced
    from repro.train import TrainConfig, Trainer

    cfg = reduced(get_config("qwen1.5-0.5b"), vocab=32, n_layers=2,
                  d_model=64, d_ff=128)
    data = {"tokens": jnp.asarray(
        np.random.RandomState(0).randint(0, 32, (4, 16)))}
    outs = []
    for overlap in (False, True):
        tcfg = TrainConfig(lr=1e-2, total_steps=1, overlap=overlap,
                           bucket_mb=0.001)
        tr = Trainer(cfg, tcfg)
        params, opt = tr.init_state(seed=0)
        step = tr._make_step()
        p2, _, metrics = step(params, opt, data)
        outs.append((p2, metrics))
    (pa, ma), (pb, mb) = outs
    assert float(ma["loss"]) == float(mb["loss"])
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.mesh
def test_trainer_overlap_step_on_mesh():
    """The overlap taps' replicated-pin branch under a real multi-device
    mesh: the step must run and match the plain step's loss."""
    out = run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models import reduced
    from repro.train import TrainConfig, Trainer
    cfg = reduced(get_config("qwen1.5-0.5b"), vocab=32, n_layers=2,
                  d_model=64, d_ff=128)
    data = {"tokens": jnp.asarray(
        np.random.RandomState(0).randint(0, 32, (8, 16)))}
    from repro.dist import make_mesh
    mesh = make_mesh((4, 4), ("data", "model"))
    losses = []
    with jax.set_mesh(mesh):
        for overlap in (False, True):
            tr = Trainer(cfg, TrainConfig(overlap=overlap, bucket_mb=0.001))
            params, opt = tr.init_state(seed=0)
            _, _, metrics = tr._make_step()(params, opt, data)
            losses.append(float(metrics["loss"]))
    assert abs(losses[0] - losses[1]) < 1e-5, losses
    print("OVERLAP_MESH_OK")
    """)
    assert "OVERLAP_MESH_OK" in out


# ---------------------------------------------------------------------------
# per-key KVStore byte attribution (the analytic side of the bucketed
# cross-validation in benchmarks/bench_dist.py)

def test_kvstore_dist_per_key_attribution():
    from repro.core import KVStoreDist
    kv = KVStoreDist(n_machines=2, devices_per_machine=4,
                     consistency="sequential")
    sizes = {"bucket0": 1024, "bucket1": 512}
    for k, n in sizes.items():
        kv.init(k, np.zeros(n, np.float32))
    for w in range(8):
        for k, n in sizes.items():
            kv.push(k, worker=w, grad=np.ones(n, np.float32))
    assert sum(kv.bytes_l1_by_key.values()) == kv.bytes_l1
    assert sum(kv.bytes_l2_by_key.values()) == kv.bytes_l2
    for k, n in sizes.items():
        assert kv.bytes_l1_by_key[k] == 8 * n * 4
        assert kv.bytes_l2_by_key[k] == 2 * n * 4
        assert kv.bytes_l1_by_key[k] == 4 * kv.bytes_l2_by_key[k]


def test_kvstore_local_per_key_attribution():
    from repro.core import KVStoreLocal, NDArray, reset_default_engine
    eng = reset_default_engine()
    kv = KVStoreLocal(eng)
    kv.init("a", np.zeros(16, np.float32))
    kv.init("b", np.zeros(4, np.float32))
    kv.push("a", NDArray(np.ones(16, np.float32), engine=eng))
    kv.push("b", NDArray(np.ones(4, np.float32), engine=eng))
    kv.push("a", NDArray(np.ones(16, np.float32), engine=eng))
    assert kv.bytes_pushed_by_key["a"] == 2 * 16 * 4
    assert kv.bytes_pushed_by_key["b"] == 4 * 4
    assert sum(kv.bytes_pushed_by_key.values()) == kv.bytes_pushed
