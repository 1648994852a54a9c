"""Eventual-consistency gradient sync (DESIGN.md §15): the bounded-
staleness schedule, its analytic byte/state models, and the on-mesh
staleness-0 bit-exactness gate."""
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).parent))
from hypothesis_compat import given, settings, st  # noqa: E402
from mesh_subproc import run_sub  # noqa: E402

from repro.dist import make_mesh
from repro.dist.bucketing import BucketPlan
from repro.dist.collectives import (EventualSync, eventual_crosspod_bytes,
                                    eventual_state_bytes,
                                    eventual_sync_buckets)


def _plan(n_leaves=6, elems=1000, cap=4096):
    leaves = [jax.ShapeDtypeStruct((elems,), "float32")
              for _ in range(n_leaves)]
    return BucketPlan.build(leaves, cap_bytes=cap)


# ---------------------------------------------------------------------------
# schedule

def test_schedule_round_robin():
    assert eventual_sync_buckets(4, 0, 0) == (0, 1, 2, 3)
    assert eventual_sync_buckets(4, 1, 0) == (0, 2)
    assert eventual_sync_buckets(4, 1, 1) == (1, 3)
    assert eventual_sync_buckets(4, 3, 2) == (2,)
    assert eventual_sync_buckets(4, 3, 1, warm=True) == (0, 1, 2, 3)


def test_schedule_covers_every_bucket_once_per_period():
    for n, ms in [(1, 0), (3, 1), (4, 2), (7, 5), (5, 9)]:
        period = ms + 1
        seen = []
        for p in range(period):
            seen.extend(eventual_sync_buckets(n, ms, p))
        assert sorted(seen) == list(range(n)), (n, ms, seen)


@settings(max_examples=200, deadline=None)
@given(st.integers(1, 12), st.integers(0, 8), st.integers(0, 60))
def test_staleness_never_exceeds_bound(n_buckets, max_staleness, n_steps):
    """Property: running the host-side schedule for any number of steps,
    every bucket's observed staleness (steps since its last scheduled
    sync) stays <= max_staleness."""
    versions = [None] * n_buckets
    for step in range(n_steps):
        warm = step == 0
        synced = set(eventual_sync_buckets(n_buckets, max_staleness,
                                           step % (max_staleness + 1),
                                           warm=warm))
        for b in range(n_buckets):
            if b in synced or versions[b] is None:
                versions[b] = step
            else:
                assert step - versions[b] <= max_staleness, \
                    (b, step, versions[b])


def test_record_step_tracks_observed_staleness():
    # EventualSync on a 1-device host degenerates (no pod axis), so the
    # host-side bookkeeping is exercised through the schedule directly
    # (run_sub covers the on-mesh variant); here: versions math only.
    versions = [None] * 4
    max_obs = 0
    for step in range(9):
        synced = set(eventual_sync_buckets(4, 2, step % 3, warm=step == 0))
        for b in range(4):
            if b in synced or versions[b] is None:
                versions[b] = step
            else:
                max_obs = max(max_obs, step - versions[b])
    assert max_obs == 2


# ---------------------------------------------------------------------------
# analytic models (pure, no mesh)

def test_crosspod_bytes_sum_over_phases_equals_full_sync():
    plan = _plan()
    for n_data in (1, 2, 4):
        for ms in (0, 1, 2, 5):
            total = sum(eventual_crosspod_bytes(plan, n_data,
                                                max_staleness=ms, phase=p)
                        for p in range(ms + 1))
            full = eventual_crosspod_bytes(plan, n_data, max_staleness=ms,
                                           phase=0, warm=True)
            assert total == full, (n_data, ms)
            # warm == the staleness-0 every-step (sequential) total
            assert full == eventual_crosspod_bytes(plan, n_data,
                                                   max_staleness=0, phase=0)


def test_state_bytes_is_one_shard_per_bucket_per_worker():
    plan = _plan(n_leaves=3, elems=1001, cap=1 << 20)
    out = eventual_state_bytes(plan, n_data=4, n_workers=8)
    shard = -(-3 * 1001 // 4) * 4           # padded 1/n_data shard, f32
    assert out["per_worker"] == shard
    assert out["total"] == shard * 8
    assert out["n_buckets"] == 1


def test_memplan_model_matches_collectives_model():
    from repro.core.memplan import eventual_sync_bytes
    leaves = [((1000,), "float32")] * 6
    out = eventual_sync_bytes(leaves, n_data=4, n_workers=8,
                              max_staleness=2, bucket_bytes=4096)
    plan = _plan()
    assert out["per_worker"] == eventual_state_bytes(
        plan, 4, 8)["per_worker"]
    assert out["crosspod_reduction"] == pytest.approx(3.0)


def test_eventual_sync_validates_args():
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="max_staleness"):
        EventualSync(mesh, {"w": jax.ShapeDtypeStruct((1, 8), "float32")},
                     max_staleness=-1)


def test_degenerate_on_single_worker_mesh():
    mesh = make_mesh((1, 1), ("data", "model"))
    ev = EventualSync(mesh, {"w": jax.ShapeDtypeStruct((1, 8), "float32")},
                      max_staleness=3)
    assert ev.degenerate
    assert ev.init_state() == {}
    assert ev.crosspod_allreduce_bytes(0) == 0
    assert ev.state_bytes()["total"] == 0
    # degenerate schedule: every bucket "syncs" every step
    assert ev.sync_buckets(2) == tuple(range(ev.n_buckets))


# ---------------------------------------------------------------------------
# on-mesh (subprocess, 16 devices: 2 pods x 4 data x 2 model)

@pytest.mark.mesh
def test_staleness0_bit_exact_and_hlo_bytes_on_mesh():
    """Eventual at staleness 0 == bucketed bit-for-bit (warm AND steady
    state), and each phase's compiled cross-pod all-reduce bytes equal
    the analytic model exactly."""
    out = run_sub("""
    import functools
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist.collectives import EventualSync, gradient_sync
    from repro.launch.dryrun import collective_bytes

    from repro.dist import make_mesh
    mesh = make_mesh((2, 4, 2), ("pod", "data", "model"))
    W = 8
    rng = np.random.default_rng(0)
    g = {f"w{i}": jnp.asarray(rng.normal(size=(W, 700 + 100 * i)),
                              jnp.float32) for i in range(4)}

    ev0 = EventualSync(mesh, g, max_staleness=0, bucket_bytes=4096)
    s = ev0.init_state()
    ref = gradient_sync(mesh, g, mode="bucketed", plan=ev0.plan)
    for warm in (True, False):
        out, s = ev0.apply(g, s, phase=0, warm=warm)
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
            assert (np.asarray(a) == np.asarray(b)).all(), "not bit-exact"
    print("BIT_EXACT_OK")

    ev = EventualSync(mesh, g, max_staleness=2, bucket_bytes=4096)
    state = ev.init_state()
    for phase in range(ev.period):
        f = jax.jit(functools.partial(
            lambda p, x, s: ev.apply(x, s, phase=p), phase))
        coll = collective_bytes(f.lower(g, state).compile().as_text())
        want = ev.crosspod_allreduce_bytes(phase)
        assert coll['raw']['all-reduce'] == want, (phase, coll, want)
    print("HLO_BYTES_OK")
    """)
    assert "BIT_EXACT_OK" in out and "HLO_BYTES_OK" in out


@pytest.mark.mesh
def test_trainer_eventual_staleness0_matches_sequential():
    """Through the Trainer: sync_mode='eventual' at staleness 0 produces
    bit-identical params to sync_mode='sequential' on a (2,4,1) mesh."""
    out = run_sub("""
    import jax, numpy as np
    from repro.configs import get_config
    from repro.models import reduced
    from repro.train import TrainConfig, Trainer
    from repro.data import SyntheticLM

    cfg = reduced(get_config("qwen1.5-0.5b"), vocab=32, n_layers=2,
                  d_model=64, d_ff=128)
    from repro.dist import make_mesh
    mesh = make_mesh((2, 4, 1), ("pod", "data", "model"))

    def run(mode, ms=0):
        data = SyntheticLM(32, 16, 8, seed=1, n_batches=3)
        tcfg = TrainConfig(lr=1e-2, total_steps=3, log_every=10,
                           sync_mode=mode, max_staleness=ms,
                           bucket_mb=0.001)
        with jax.set_mesh(mesh):
            tr = Trainer(cfg, tcfg)
            params, _ = tr.fit(data, seed=0)
        return tr, params

    _, p_seq = run("sequential")
    tr_ev, p_ev = run("eventual")
    for a, b in zip(jax.tree.leaves(p_seq), jax.tree.leaves(p_ev)):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert tr_ev._ev.max_observed_staleness == 0
    tr2, p2 = run("eventual", ms=2)
    assert tr2._ev.max_observed_staleness <= 2
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(p2))
    print("TRAINER_EVENTUAL_OK")
    """, devices=8)
    assert "TRAINER_EVENTUAL_OK" in out
