"""The trace reduction: interval arithmetic on a hand-made trace, and the
whole reduction on a small trace recorded on a TPU v5e and committed
beside this file."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def _trace():
    ops = [(0, 10, "fusion.1"), (10, 20, "all-reduce.3"), (30, 40, "dot.2"),
           (40, 60, "all-reduce.4"), (70, 80, "fusion.1")]
    dev = tr.DeviceOps("/device:TPU:0", ops,
                       busy=tr.merge((s, e) for s, e, _ in ops))
    host = [(0, 25, "bench_step"), (12, 18, "decode_step"),
            (60, 85, "bench_step")]
    return tr.Trace([dev], host)


def test_merge_subtract_clip():
    assert tr.merge([(5, 8), (0, 3), (2, 4), (8, 9)]) == [(0, 4), (5, 9)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_busy_ops_exposed_collectives_and_gaps():
    t = _trace()
    # busy: [0, 20] + [30, 60] + [70, 80] = 60 ns of the 100 ns window
    assert tr.busy_s(t, 0, 100) == pytest.approx(60e-9)
    ops = tr.op_seconds(t, 0, 100)
    assert ops["fusion.1"] == pytest.approx(20e-9)
    # no compute runs beside either all-reduce: 10 + 20 ns exposed
    assert tr.exposed_collective_s(t, 0, 100) == pytest.approx(30e-9)
    gaps = tr.idle_gaps(t, 0, 100, t.host)
    assert gaps[0] == ["outside any host span", pytest.approx(20e-9)]
    labels = {g[0] for g in gaps}
    assert "bench_step" in labels          # (20, 30) inside bench_step
    assert tr.top_ops(t, 0, 100)[0][0] == "all-reduce"
    assert tr.top_ops(t, 0, 100)[1] == ["fusion", pytest.approx(20e-9)]


def test_nested_ops_self_time_and_leaves():
    ops = [(0, 100, "while.5"), (10, 30, "copy.1"), (40, 60, "all-reduce.2"),
           (70, 90, "fusion.3")]
    dev = tr.DeviceOps("/device:TPU:0", ops, [(0, 100, "jit_step(1)")],
                       busy=tr.merge((s, e) for s, e, _ in ops))
    t = tr.Trace([dev], [])
    own = tr.self_seconds(t, 0, 100)
    assert own["while"] == pytest.approx(40e-9)     # 100 - 3 x 20
    assert own["copy"] == pytest.approx(20e-9)
    assert [n for _, _, n in tr.leaves(ops)] == ["copy.1", "all-reduce.2",
                                                 "fusion.3"]
    # the loop holding the collective does not hide it
    assert tr.exposed_collective_s(t, 0, 100) == pytest.approx(20e-9)
    assert tr.module_seconds(t, 0, 50, "jit_step") == pytest.approx(50e-9)
    assert tr.short_name("%copy.75 = bf16[2] copy(x)") == "copy.75"


def test_clock_offset_pairs_annotations_with_host_starts():
    t = _trace()
    off = tr.clock_offset_ns(t, "bench_step", [1.0, 1.00000006])
    assert off == pytest.approx(-1e9, abs=1)


def test_recorded_chip_trace():
    """Four engine steps of the chat cell's engine (qwen1.5-0.5b, 64 lanes)
    traced on a TPU v5e, each step inside a ``bench_engine_step``
    annotation."""
    t = tr.load(DATA / "chat_steps.xplane.pb")
    assert t.devices and t.devices[0].name.startswith("/device:TPU:0")
    steps = [(s, e) for s, e, n in t.host if n == "bench_engine_step"]
    assert len(steps) == 4
    t0, t1 = steps[0][0], steps[-1][1]
    busy = tr.busy_s(t, t0, t1)
    assert 0 < busy <= (t1 - t0) / 1e9
    ops = tr.op_seconds(t, t0, t1)
    assert any(name.startswith("paged_attention") for name in ops)
    assert tr.module_seconds(t, t0, t1, "jit_decode_paged") > 0
    assert tr.module_seconds(t, t0, t1, "jit_sample_tokens") > 0
    own = tr.self_seconds(t, t0, t1)
    # self times partition the busy time: no op counted twice
    assert sum(own.values()) == pytest.approx(busy, rel=0.05)
    assert "paged_attention" in own and "copy" in own
    gaps = tr.idle_gaps(t, t0, t1, [(s, e, "bench_engine_step")
                                    for s, e in steps])
    assert gaps and all(g[1] > 0 for g in gaps)
