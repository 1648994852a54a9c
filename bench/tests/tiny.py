"""Cells at the repo's ``reduced()`` sizes for CPU rehearsals: the real
configuration and mix files with every size cut down, so each driver runs
end to end in seconds."""
from __future__ import annotations

import copy
from types import SimpleNamespace

from bench import common

SMALL = {"num_hidden_layers": 2, "hidden_size": 256,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 64, "intermediate_size": 512, "vocab_size": 512}


def tiny_cell(workload: str) -> common.Cell:
    cell = common.load_cell(workload)
    conf = copy.deepcopy(cell.config)
    conf["model"].update(SMALL)
    conf["reduced"] = sorted(SMALL)
    m = conf["model"]
    per_tok = (m["num_hidden_layers"] * 2 * m["num_key_value_heads"]
               * m["head_dim"] * 2)
    conf["kv_pool"] = {"bytes_per_token": per_tok, "num_blocks": 160,
                       "block_size": 16}
    conf["engine"] = {"block_size": 16, "max_batch": 4, "prefill_chunk": 32,
                      "max_len": 256}
    mix = copy.deepcopy(cell.mix)
    if mix["kind"] in ("open_loop", "backlog"):
        mix["prompt"] = {"median": 40, "sigma": 0.6, "min": 8, "max": 120}
        mix["output"] = {"median": 10, "sigma": 0.5, "min": 4, "max": 32}
        mix["check"] = {"tokens": 40}
    if mix["kind"] == "open_loop":
        mix.update(rate_per_s=4.0, warm_s=1.0)
    if mix["kind"] == "backlog":
        mix.update(requests=200, queue_depth=8)
    if mix["kind"] == "train":
        mix.update(batch_per_chip=2, seq=64)
    cell.config, cell.mix = conf, mix
    # a loss near ln(512) over 128 tokens moves more under bf16 rounding
    # than one near ln(151936) over 16k tokens
    if "loss_rel_gap" in cell.limits:
        cell.limits = dict(cell.limits, loss_rel_gap=1e-3)
    # at width 256 the fp8 control's widest sampled gap over ~150 tokens
    # is 0.07-0.10 (0.30 and up at the chat cell's widths), the program's
    # under 0.02: the limit for this size lies between them
    if cell.mix["kind"] == "open_loop":
        cell.limits = dict(cell.limits, logit_gap=0.04)
    return cell


def args(workload, seed=7, seconds=2.0, trace=0):
    return SimpleNamespace(workload=workload, seed=seed, seconds=seconds,
                           trace=trace)


CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
