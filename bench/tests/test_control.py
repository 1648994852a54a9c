"""The control, at a size a test run holds: the reference computed in fp8
in the program's place reads well above the program on the numbers that
decide ``correct``, and through the same comparison, at the committed
limits, it comes out as not correct.  (On the chip, at the cells' own
sizes, the readings that set each limit are in PERF.md.)"""
from __future__ import annotations

import pytest

from bench import calibrate, common
from bench.tests import tiny

common.use_src_path()


def _readings(workload):
    cell = tiny.tiny_cell(workload)
    seconds = 2.0
    if cell.mix["kind"] == "open_loop":
        # a widest gap grows with the tokens read: as many as fit a test
        cell.mix["check"]["tokens"], seconds = 150, 5.0
    rows, summary = calibrate.calibrate(cell, [31, 32], seconds, True, None,
                                        tiny.CPU_DEVICE)
    assert all(r["correct"] for r in rows), rows
    assert all(r["control_correct"] is False for r in rows), rows
    assert summary["control_correct_any"] is False
    return summary


@pytest.mark.parametrize("workload", ["qwen1.5-0.5b.chat",
                                      "starcoder2-15b-pp4.code-backlog"])
def test_serving_control_reads_far_above_the_program(workload):
    s = _readings(workload)
    for key in tiny.tiny_cell(workload).limits:     # the numbers compared
        assert s["control_min"][key] > 3 * s["max"][key], (key, s)


def test_training_control_reads_far_above_the_program():
    s = _readings("qwen1.5-0.5b.train-4k")
    for key in ("grad_leaf_gap", "change_leaf_gap"):
        assert s["control_min"][key] > 3 * s["max"][key], (key, s)
