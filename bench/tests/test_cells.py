"""Every cell rehearsed end to end on the CPU at ``reduced()`` size: the
drivers, the check and the result line; with a fault planted in the timed
path ``correct`` comes out false; and with no TPU the entry point exits
nonzero without a result line."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import calibrate, common, run_cell
from bench.tests import tiny

common.use_src_path()

SERVE = ["qwen1.5-0.5b.chat", "starcoder2-15b-pp4.code-backlog"]
TRAIN = ["qwen1.5-0.5b.train-4k"]


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    """Unit peaks for the CPU, which ``bench/peaks.json`` rightly lacks."""
    monkeypatch.setattr(common, "peaks_for", lambda kind: {
        "bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})


def _run(workload, capsys, trace=0, seconds=2.0, **kw):
    cell = tiny.tiny_cell(workload)
    rc = run_cell.run(cell, tiny.args(workload, trace=trace,
                                      seconds=seconds),
                      tiny.CPU_DEVICE, **kw)
    out = capsys.readouterr()
    assert rc == 0
    return cell, json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("workload", SERVE + TRAIN)
def test_cell_rehearsal_prints_the_result_line(workload, capsys):
    cell, res, err = _run(workload, capsys)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    last = err.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") and " limit " in line
               for line in last)


@pytest.mark.parametrize("workload,span_metric", [
    ("qwen1.5-0.5b.chat", "sched_host_ms_per_step.chat"),
    ("qwen1.5-0.5b.train-4k", "data_wait_ms_per_step")])
def test_traced_rehearsal_reports_per_layer_metrics(workload, span_metric,
                                                    capsys):
    cell, res, _ = _run(workload, capsys, trace=1)
    names = {m["name"] for m in cell.per_layer}
    assert set(res["metrics"]) <= names
    assert span_metric in res["metrics"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _faulty(workload, fault, **kw):
    cell = tiny.tiny_cell(workload)
    rows, _ = calibrate.calibrate(cell, [21], 2.0, False, fault,
                                  tiny.CPU_DEVICE, **kw)
    return rows[0]


@pytest.mark.parametrize("workload", SERVE)
def test_a_token_altered_where_produced_is_not_correct(workload):
    assert _faulty(workload, "token")["correct"] is False


@pytest.mark.parametrize("fault", ["unchanged", "half"])
def test_a_broken_train_step_is_not_correct(fault):
    row = _faulty("qwen1.5-0.5b.train-4k", fault)
    assert row["correct"] is False, row["checks"]


def _entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run_cell.py", "--workload",
         "qwen1.5-0.5b.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(proc):
    return not any(line.startswith("{") and '"correct"' in line
                   for line in proc.stdout.splitlines())


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _entry(common.ROOT)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(common.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(common.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _entry(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc)
