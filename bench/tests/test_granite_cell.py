"""The granite RAG chat cell rehearsed end to end on the CPU at a tiny
size: the driver, the check against ``granite_hybrid`` and the result
line; traced, the per-layer metrics the program's spans and counts give
(the device-trace ones need a chip); and its readers on a hand-made
window."""
from __future__ import annotations

import copy
import json
from types import SimpleNamespace

import pytest

from bench import common, run_cell
from bench.tests import tiny

common.use_src_path()

WORKLOAD = "granite-4.0-h-small-pp4-ep8.rag-chat"


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(common, "peaks_for", lambda kind: {
        "bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})


def tiny_cell() -> common.Cell:
    cell = common.load_cell(WORKLOAD)
    conf = copy.deepcopy(cell.config)
    conf["model"].update(
        hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
        head_dim=64, intermediate_size=128, shared_intermediate_size=192,
        num_routed_experts=8, num_local_experts=4, num_experts_per_tok=3,
        mamba_d_state=16, mamba_n_heads=8, mamba_d_head=64,
        mamba_chunk_size=16, vocab_size=512, dtype="float32")
    conf["kv_pool"].update(bytes_per_token=1024, num_blocks=160,
                           state_bytes_per_lane=9 * (3 * 544 * 4
                                                     + 8 * 64 * 16 * 4))
    conf["engine"] = {"block_size": 16, "max_batch": 4, "prefill_chunk": 32,
                      "max_len": 256}
    cell.config = conf
    cell.mix = dict(cell.mix, rate_per_s=4.0, warm_s=1.0,
                    prompt={"median": 40, "sigma": 0.6, "min": 8, "max": 120},
                    output={"median": 10, "sigma": 0.5, "min": 4, "max": 32},
                    check={"tokens": 40})
    return cell


def _run(capsys, trace):
    cell = tiny_cell()
    rc = run_cell.run(cell, SimpleNamespace(workload=WORKLOAD,
                                            seed=2 ** 33 + 5, seconds=2.0,
                                            trace=trace), tiny.CPU_DEVICE)
    assert rc == 0
    return cell, json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_rehearsal_prints_the_result_line(capsys):
    cell, res = _run(capsys, 0)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tpot_p95_ms", "setup_s"}
    assert set(res["checks"]) == {"mean_logit_gap", "checked_tokens"}


def test_traced_rehearsal_reads_the_engines_counts(capsys):
    cell, res = _run(capsys, 1)
    m = res["metrics"]
    assert {"serve_mfu.rag", "expert_rows_per_step.rag",
            "window_compiles.rag"} <= set(m)
    assert m["expert_rows_per_step.rag"]["value"] > 0
    assert m["window_compiles.rag"]["value"] == 0
    names = {p["name"] for p in cell.per_layer}
    assert names == {"expert_gmm_roofline.rag", "decode_roofline.rag",
                     "serve_mfu.rag", "expert_rows_per_step.rag",
                     "sched_idle_ms_per_step.rag", "device_idle_share.rag",
                     "window_compiles.rag"}


def _ctx(steps, spans):
    from bench.run_cell import ReadCtx
    from bench.serve_driver import StepRec
    cell = common.load_cell(WORKLOAD)
    trace = SimpleNamespace(devices=[], host=[])
    return ReadCtx(cell=cell, dims=cell.dims,
                   peaks={"bf16_flops_per_s": 197e12,
                          "hbm_bytes_per_s": 819e9},
                   chips=1, window=(0.0, 10.0), dev_window=(0.0, 1e10),
                   steps=[StepRec(0.1 * i, 0.1 * i + 0.05, [], c)
                          for i, c in enumerate(steps)],
                   spans=spans, trace=trace, run={})


def test_readers_on_a_hand_made_window(monkeypatch):
    from bench import readers, trace_reduce
    ref = common.load_reference("granite_hybrid")
    gmm = common.load_module(common.BENCH / "metrics"
                             / "expert_gmm_roofline.rag.py", "t_")
    dec = common.load_module(common.BENCH / "metrics"
                             / "decode_roofline.rag.py", "t_")
    ctxs = [[2048 + j for j in range(60)]] * 4
    spans = [(0.1 * i, 0.1 * i + 0.05, "engine_step",
              {"lanes": 60, "prefill_tokens": 512, "expert_rows": 6000,
               "expert_rows_max": 30}) for i in range(4)]
    ctx = _ctx(ctxs, spans)
    d = ctx.dims
    # the kernel took twice its least time; the decode program 4x its own
    f, b = gmm.cost(d, 6000, 2 * d["L"])
    least = max(f / 197e12, b / 819e9)
    monkeypatch.setattr(readers, "kernel_seconds",
                        lambda c, mark: 4 * 2 * least)
    got = gmm.read(ctx)
    assert got["value"] == pytest.approx(50.0) and got["bound"] == "memory"
    step = dec.least_bytes(ref, d, ctxs[0]) / 819e9
    monkeypatch.setattr(trace_reduce, "module_seconds",
                        lambda t, t0, t1, mark: 4 * 4 * step)
    assert dec.read(ctx)["value"] == pytest.approx(25.0)
    # weights 4.83 GB, 60 lanes' states both ways 4.58 GB
    assert dec.least_bytes(ref, d, []) == ref.weight_bytes(d)
    assert ref.weight_bytes(d) == pytest.approx(4.835e9, rel=1e-3)
