"""The readers of the program's own spans (``bench/program_trace.py``):
idle time attributed to the innermost span on a hand-made trace, each new
metric in a traced CPU rehearsal of every cell, and a recompile planted
in the window read back with its function's name."""
from __future__ import annotations

import json

import pytest

from bench import (common, program_trace, run_cell, serve_driver,
                   trace_reduce, traffic)
from bench.tests import tiny

common.use_src_path()

W0 = 1.0                       # host window start, seconds
T0 = 5000.0                    # the same instant on the trace's clock, ns


def _host(ns):
    """Host-clock seconds of a trace time (ns) in the hand-made window."""
    return W0 + (ns - T0) * 1e-9


def _ctx(host, busy, spans):
    dev = trace_reduce.DeviceOps("/device:TPU:0", [(s, e, "fusion.1")
                                                   for s, e in busy],
                                 busy=trace_reduce.merge(busy))
    return run_cell.ReadCtx(cell=None, dims=None, peaks=None, chips=1,
                            window=(W0, _host(5200.0)),
                            dev_window=(T0, 5200.0), steps=[], spans=spans,
                            trace=trace_reduce.Trace([dev], sorted(host)),
                            run={})


def _hand_made():
    """Two engine steps in a 200 ns window, one more that runs past its
    end; a GC inside ``admit`` and a compile (its tracing, then its XLA
    compile) that overlaps ``batch_build``, both from the recorder on the
    host clock."""
    host = [(5000, 5100, "engine_step"), (5000, 5020, "admit"),
            (5040, 5090, "decode_step"), (5040, 5050, "decode_dispatch"),
            (5050, 5090, "decode_sync"), (5090, 5100, "retire"),
            (5120, 5200, "engine_step"), (5120, 5130, "admit"),
            (5140, 5160, "batch_build"), (5190, 5250, "engine_step"),
            (4000, 6000, "bench_step")]
    busy = [(5055, 5085), (5150, 5200)]
    spans = [(_host(5010), _host(5015), "gc", {"generation": 0}),
             (_host(5130), _host(5135), "compile",
              {"fun_name": "decode_paged", "stage": "jaxpr_trace_duration"}),
             (_host(5135), _host(5145), "compile",
              {"fun_name": "decode_paged",
               "stage": "backend_compile_duration"}),
             (_host(3000), _host(3500), "compile",
              {"fun_name": "early", "stage": "backend_compile_duration"})]
    return host, busy, spans


def test_idle_goes_to_the_innermost_span_inside_engine_steps():
    ctx = _ctx(*_hand_made())
    got = program_trace.sched_idle_ms(ctx)
    # idle [5100, 5120] lies between the steps and counts for nothing
    want_ns = {"admit": 25, "gc": 5, "unattributed": 20,
               "decode_dispatch": 10, "decode_sync": 10, "retire": 10,
               "compile": 15, "batch_build": 5}
    assert got["steps"] == 2
    assert got["value"] == pytest.approx(sum(want_ns.values()) / 2e6)
    assert got["by_phase"] == pytest.approx(
        {k: v / 2e6 for k, v in want_ns.items()})


def test_attribute_splits_a_gap_at_span_edges():
    spans = [(0, 100, "decode_step"), (10, 20, "gc"), (15, 30, "compile")]
    got = program_trace.attribute([(0, 40)], spans)
    assert got == {"decode_step": 20, "gc": 10, "compile": 10}
    assert program_trace.attribute([(200, 210)], spans) == {
        "unattributed": 10}


def test_counts_and_compiles_from_the_recorder():
    host, busy, spans = _hand_made()
    spans = spans + [
        (_host(5000), _host(5100), "engine_step",
         {"lanes": 3, "prefill_tokens": 0}),
        (_host(5120), _host(5200), "engine_step",
         {"lanes": 0, "prefill_tokens": 512}),
        (_host(5190), _host(5250), "engine_step",
         {"lanes": 9, "prefill_tokens": 9})]
    ctx = _ctx(host, busy, spans)
    assert program_trace.mean_count(ctx, "lanes", decoding_only=True) == {
        "value": 3, "steps": 1}
    assert program_trace.mean_count(ctx, "prefill_tokens")["value"] == 256
    # one XLA compile in the window, whatever stages it records
    assert program_trace.window_compiles(ctx) == {
        "value": 1, "fun_names": ["decode_paged"]}
    # a window whose host plane holds none of the spans that come with
    # compile spans: the program records none, and reads as nothing
    bare = _ctx([h for h in host if h[2] != "engine_step"], busy, spans)
    assert program_trace.window_compiles(bare) is None


def test_a_program_without_the_spans_reads_as_nothing():
    host, busy, _ = _hand_made()
    ctx = _ctx([h for h in host if h[2] == "bench_step"], busy, [])
    assert program_trace.sched_idle_ms(ctx) is None
    assert program_trace.mean_count(ctx, "lanes") is None
    assert program_trace.window_compiles(ctx) is None


@pytest.fixture
def cpu(monkeypatch, tmp_path):
    """Unit peaks for the CPU, a trace directory of the test's own, and a
    stand-in for device 0: a CPU trace has no device plane, and the CPU
    client runs each program inside its ``PjRtCpuExecutable::Execute``
    host events, so those stand in for the device's busy time."""
    monkeypatch.setattr(run_cell, "TRACE_DIR", tmp_path / "trace")
    monkeypatch.setattr(common, "peaks_for", lambda kind: {
        "bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    load = trace_reduce.load

    def with_device(path):
        t = load(path)
        if not t.devices:
            ev = [(s, e, "cpu_program") for s, e, n in t.host
                  if n.startswith("PjRtCpuExecutable::Execute")]
            t.devices = [trace_reduce.DeviceOps(
                "/device:CPU:0", ev, busy=trace_reduce.merge(
                    (s, e) for s, e, _ in ev))]
        return t

    monkeypatch.setattr(trace_reduce, "load", with_device)


def _traced(workload, capsys):
    cell = tiny.tiny_cell(workload)
    rc = run_cell.run(cell, tiny.args(workload, trace=1, seconds=2.0),
                      tiny.CPU_DEVICE)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])[
        "metrics"]


NEW = {"qwen1.5-0.5b.chat": ["sched_idle_ms_per_step.chat",
                             "decode_lanes_per_step.chat",
                             "window_compiles.chat"],
       "starcoder2-15b-pp4.code-backlog": [
           "sched_idle_ms_per_step.backlog",
           "prefill_tokens_per_step.backlog", "window_compiles.backlog"],
       "qwen1.5-0.5b.train-4k": ["window_compiles.train"]}
KNOWN = set(program_trace.PHASES) | {"compile", "gc", "unattributed"}


@pytest.mark.parametrize("workload", sorted(NEW))
def test_each_new_metric_is_in_a_traced_rehearsal(workload, cpu, capsys):
    m = _traced(workload, capsys)
    assert set(NEW[workload]) <= set(m)
    for name in NEW[workload]:
        if name.startswith("window_compiles"):
            assert m[name]["value"] == 0 and m[name]["fun_names"] == []
        elif name.startswith("sched_idle"):
            by = m[name]["by_phase"]
            assert set(by) <= KNOWN
            assert sum(by.values()) == pytest.approx(m[name]["value"])
        else:
            assert m[name]["value"] > 0


def test_a_compile_in_the_window_is_counted_once(cpu, capsys, monkeypatch):
    """A fresh program compiled between two steps of the window is one
    compile, under its function's name, though JAX records its tracing,
    lowering and XLA compile as three stages."""
    import jax
    import numpy as np
    run = serve_driver.run_open_loop

    def planted_recompile(x):
        return x * 3 + 1

    def planted(engine, reqs, mix, seconds, clock, window, step_hook=None):
        fired = []

        def compile_once():
            if window.t0 is not None and not fired:
                fired.append(True)
                jax.jit(planted_recompile)(np.ones((5, 39), np.float32))

        return run(engine, reqs, mix, seconds, clock, window,
                   step_hook=compile_once)

    monkeypatch.setattr(serve_driver, "run_open_loop", planted)
    got = _traced("qwen1.5-0.5b.chat", capsys)["window_compiles.chat"]
    assert got["value"] == 1
    assert got["fun_names"] == ["jit(planted_recompile)"]


def test_engine_step_counts_match_what_the_harness_infers():
    """The engine's own counts of a step's work equal what
    ``serve_driver.Tracker`` infers from request state around it."""
    import jax
    import numpy as np
    from repro import obs
    from repro.configs import get_config
    from repro.models import get_model, reduced
    from repro.serve import PagedServeEngine
    cfg = reduced(get_config("qwen1.5-0.5b"))
    eng = PagedServeEngine(cfg, get_model(cfg).init(jax.random.PRNGKey(0)),
                           block_size=8, max_batch=2, max_len=64,
                           prefill_chunk=8)
    eng.warmup()
    rng = np.random.RandomState(3)
    old = obs.get_recorder()
    try:
        rec = obs.set_recorder(obs.TraceRecorder(enabled=True))
        tr = serve_driver.Tracker(eng, common.Clock())
        for i, (n, out) in enumerate(zip((5, 11, 19, 9), (3, 4, 6, 2))):
            tr.add(traffic.Request(i, 0.0, list(rng.randint(1, cfg.vocab, n)),
                                   out), 0.0)
        while eng.busy:
            tr.step()
    finally:
        obs.set_recorder(old)
    counts = [e["args"] for e in rec.events() if e["name"] == "engine_step"]
    assert len(counts) == len(tr.steps)
    for c, st in zip(counts, tr.steps):
        assert c["lanes"] == len(st.decode_ctx)
        assert c["prefill_tokens"] == sum(n for _, n in st.prefill)
    assert sum(c["lanes"] for c in counts) > 0
