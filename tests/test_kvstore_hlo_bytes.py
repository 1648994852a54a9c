"""Cross-validate the §3.3 byte model: the analytic two-level KVStore
counters (``KVStoreDist.bytes_l1/bytes_l2``) against ``collective_bytes()``
parsed from the compiled ``gradient_sync`` HLO.

Both layers model the same claim — level-1 (intra-machine / intra-pod)
aggregation shrinks inter-machine traffic by the devices-per-machine
factor — so the analytic ratio and the HLO all-reduce ratio must agree.

Multi-device lowering needs --xla_force_host_platform_device_count set
before jax initializes, hence the subprocess.
"""
import numpy as np

import pytest

from mesh_subproc import run_sub
from repro.core import KVStoreDist

# topology shared by both layers: 2 machines/pods x 4 devices, 4096-float
# gradient
N_MACHINES, DEVS_PER_MACHINE, N_PARAM = 2, 4, 4096


def test_analytic_two_level_ratio():
    """bytes_l1 / bytes_l2 == devices_per_machine for one sync round."""
    kv = KVStoreDist(n_machines=N_MACHINES,
                     devices_per_machine=DEVS_PER_MACHINE,
                     consistency="sequential")
    kv.init("w", np.zeros(N_PARAM, np.float32))
    for w in range(N_MACHINES * DEVS_PER_MACHINE):
        kv.push("w", worker=w, grad=np.ones(N_PARAM, np.float32))
    assert kv.bytes_l1 == N_MACHINES * DEVS_PER_MACHINE * N_PARAM * 4
    assert kv.bytes_l2 == N_MACHINES * N_PARAM * 4
    assert kv.bytes_l1 // kv.bytes_l2 == DEVS_PER_MACHINE


@pytest.mark.mesh
def test_hlo_matches_analytic_ratio():
    """The compiled hierarchical schedule's cross-pod all-reduce carries
    1/devices_per_machine of the flat schedule's bytes — the same factor
    the analytic counters predict."""
    out = run_sub(f"""
    import jax, jax.numpy as jnp
    from repro.dist.collectives import gradient_sync
    from repro.launch.dryrun import collective_bytes
    from repro.dist import make_mesh
    mesh = make_mesh(({N_MACHINES}, {DEVS_PER_MACHINE}, 2),
                         ("pod", "data", "model"))
    W = {N_MACHINES * DEVS_PER_MACHINE}
    g = {{"w": jnp.zeros((W, {N_PARAM}), jnp.float32)}}
    with jax.set_mesh(mesh):
        coll = {{}}
        for mode in ("flat", "hierarchical"):
            txt = jax.jit(
                lambda x, mode=mode: gradient_sync(mesh, x, mode=mode)
            ).lower(g).compile().as_text()
            coll[mode] = collective_bytes(txt)
    flat_ar = coll["flat"]["raw"]["all-reduce"]
    hier_ar = coll["hierarchical"]["raw"]["all-reduce"]
    assert flat_ar == {N_PARAM} * 4, coll["flat"]
    assert hier_ar == {N_PARAM} * 4 // {DEVS_PER_MACHINE}, coll["hierarchical"]
    # the level-1 reduction traffic moved off the pod boundary onto
    # intra-pod collectives, which must therefore be present
    assert coll["hierarchical"]["counts"]["all-to-all"] >= 1
    assert coll["hierarchical"]["counts"]["all-gather"] >= 1
    print("RATIO", flat_ar // hier_ar)
    """)
    # HLO factor == analytic factor == devices-per-machine
    assert f"RATIO {DEVS_PER_MACHINE}" in out
