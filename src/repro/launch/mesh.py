"""Production mesh construction + the multi-host entry point.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state.  Single pod: 256 chips (16 data × 16 model).
Multi-pod: 2 pods × 256 = 512 chips with a leading "pod" axis.

Multi-host (DESIGN.md §15): :func:`initialize_distributed` joins this
process to a ``jax.distributed`` group — addressing comes from explicit
arguments, or the ``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCS`` /
``REPRO_PROC_ID`` environment (what ``repro.launch.multihost`` exports to
its workers).  :func:`make_distributed_mesh` then builds a process-major
``(pod, data, model)`` mesh over the job's global devices, so the same
dryrun meshes run on real pods and on N local CPU processes.
"""
from __future__ import annotations

import os

import jax
import numpy as np

from repro.dist.compat import make_mesh


def initialize_distributed(coordinator_address: str | None = None,
                           num_processes: int | None = None,
                           process_id: int | None = None) -> bool:
    """Join (or no-op re-join) a ``jax.distributed`` process group.

    Arguments fall back to the ``REPRO_COORDINATOR`` /
    ``REPRO_NUM_PROCS`` / ``REPRO_PROC_ID`` environment; with neither,
    the call is the single-process identity (returns False).  Safe to
    call twice — an already-initialized group is left untouched.  On the
    CPU backend the gloo collectives implementation is selected so
    cross-process psums actually work (the per-process device count is an
    *environment* matter: set ``XLA_FLAGS=--xla_force_host_platform_-
    device_count=L`` before the first jax use, as the multihost launcher
    does for its workers).
    """
    coordinator_address = (coordinator_address
                           or os.environ.get("REPRO_COORDINATOR"))
    if num_processes is None and "REPRO_NUM_PROCS" in os.environ:
        num_processes = int(os.environ["REPRO_NUM_PROCS"])
    if process_id is None and "REPRO_PROC_ID" in os.environ:
        process_id = int(os.environ["REPRO_PROC_ID"])
    if coordinator_address is None:
        return False
    if num_processes is None or process_id is None:
        raise ValueError("distributed init needs num_processes and "
                         "process_id alongside the coordinator address")
    from jax._src import distributed as _dist
    if getattr(_dist.global_state, "client", None) is not None:
        return True     # already in a group
    # CPU collectives backend: only gloo supports cross-process
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


def make_distributed_mesh(*, model_axis: int = 1):
    """Process-major ``(pod, data, model)`` mesh over every device of the
    current ``jax.distributed`` job: the pod axis IS the process index
    (each host's local devices form its data×model block), so per-host
    batch slices drop into the global batch with no resharding and the
    eventual-consistency pod boundary coincides with the host boundary.
    """
    procs = jax.process_count()
    devs = jax.devices()
    local = len(devs) // procs
    if local * procs != len(devs):
        raise ValueError(f"{len(devs)} devices do not split over "
                         f"{procs} processes")
    if model_axis < 1 or local % model_axis:
        raise ValueError(f"model_axis {model_axis} must divide the "
                         f"per-process device count {local}")
    shape = (procs, local // model_axis, model_axis)
    # plain reshape, NOT mesh_utils.create_device_mesh: jax.devices() is
    # process-major, and keeping that order is the whole point
    return jax.sharding.Mesh(np.array(devs).reshape(shape),
                             ("pod", "data", "model"))


def make_production_mesh(*, multi_pod: bool = False, pp_stages: int = 1,
                         distributed: bool = False):
    """``pp_stages > 1`` carves a leading ``stage`` axis out of the data
    axis (DESIGN.md §10): chips-per-pod stays 256, the gradient-worker
    count shrinks to ``16 // pp_stages`` — the stage axis carries layer
    groups, not replicas.

    ``distributed=True`` runs :func:`initialize_distributed` (env
    addressing) first, so the same 256/512-chip shapes assemble from a
    real multi-host job's global devices; the device count must still
    match the production topology — for arbitrary process×device
    geometries (CI's N-process CPU runs) use :func:`make_distributed_mesh`.
    """
    if distributed:
        initialize_distributed()
    if pp_stages < 1 or 16 % pp_stages:
        raise ValueError(f"pp_stages must divide the 16-way data axis, "
                         f"got {pp_stages}")
    shape = (16 // pp_stages, 16)
    axes = ("data", "model")
    if pp_stages > 1:
        shape = (pp_stages,) + shape
        axes = ("stage",) + axes
    if multi_pod:
        shape = (2,) + shape
        axes = ("pod",) + axes
    return make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke runs of the pjit code path."""
    return make_mesh((1, 1), ("data", "model"))


# TPU v5e hardware constants (roofline targets; DESIGN.md §6)
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link
CHIPS_PER_POD = 256
