"""The four-chip data-parallel training cell on four virtual CPU devices
(``dp4_rehearsal.py``, in a process of its own): it runs ``correct`` and
reports its end-to-end and per-layer metrics, the gradient exchange is
seen in its trace by name, each fault a data-parallel step can have comes
out not ``correct``, and the reference split over the devices reads what
it reads on one."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench import common

PER_LAYER = {"exposed_collective_ms_per_step.dp4", "train_mfu.dp4",
             "device_idle_share.dp4"}


@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-m", "bench.tests.dp4_rehearsal"],
                          cwd=common.ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.mesh
def test_dp4_runs_correct_and_reports_its_metrics(rehearsal):
    assert rehearsal["devices"] == 4
    plain, traced = rehearsal["plain"], rehearsal["traced"]
    for res in (plain, traced):
        assert res["correct"] is True, res["checks"]
        assert res["attempted"] > 0 and res["failed"] == 0
    assert set(plain["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in plain["metrics"].values())
    m = traced["metrics"]
    assert set(m) == PER_LAYER
    exposed = m["exposed_collective_ms_per_step.dp4"]
    assert exposed["collective_ops"] >= 1          # the all-reduce, by name
    # exposed time is a part of the collectives' time (equal to rounding
    # where nothing runs beside them)
    assert 0 <= exposed["value"] <= exposed["collective_ms_per_step"] * (
        1 + 1e-9)
    assert m["train_mfu.dp4"]["value"] > 0
    assert 0 <= m["device_idle_share.dp4"]["value"] < 100


@pytest.mark.mesh
@pytest.mark.parametrize("fault", ["exchange", "half", "unchanged"])
def test_a_broken_data_parallel_step_is_not_correct(rehearsal, fault):
    row = rehearsal["faults"][fault]
    assert row["correct"] is False, row["checks"]


@pytest.mark.mesh
def test_the_reference_split_over_devices_reads_as_on_one(rehearsal):
    gaps = rehearsal["split_reference"]
    assert max(gaps.values()) < 1e-4, gaps
