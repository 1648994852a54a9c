"""The one traffic generator: every mix file's parameters become a list of
requests (due time, prompt, output budget) drawn from the seed.

Sizes and gaps are stratified: ``n`` draws are the distribution's
quantiles at (i + 0.5) / n, which the seed only shuffles.  Every seed
therefore offers the same set of prompt lengths, output lengths and
inter-arrival gaps in another order, in the measured window as in the
whole run, so two seeds differ in which request comes when, not in how
much work there is.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Request:
    idx: int
    due: float            # seconds after the traffic starts
    prompt: list          # token ids
    max_new: int


def stratified_lognormal(n: int, spec: dict) -> np.ndarray:
    """``n`` lognormal quantiles (median ``spec['median']``, sigma
    ``spec['sigma']``), clipped to [min, max] and rounded to tokens."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(int)


def stratified_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` exponential inter-arrival gaps of a Poisson process at
    ``rate`` per second, as quantiles."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def segments(mix: dict, seconds: float) -> list[tuple[float, float, int]]:
    """(start, duration, requests) of an open-loop mix's warm-up and
    window, each holding its duration times ``rate_per_s`` requests."""
    out, t0 = [], 0.0
    for dur in (mix["warm_s"], seconds):
        out.append((t0, dur, int(round(mix["rate_per_s"] * dur))))
        t0 += dur
    return out


def make_requests(mix: dict, vocab: int, seed: int,
                  seconds: float) -> list[Request]:
    """The mix's requests.  ``open_loop`` draws each segment (warm-up,
    the ``seconds`` window) on its own: its requests fall inside it
    at Poisson spacings, so the window holds the same count and the same
    sizes for every seed.  ``backlog`` makes ``requests`` due at 0, drawn
    in blocks of ``queue_depth`` (the queue the harness keeps), each
    holding the same sizes, so a window sees the same mix for every
    seed."""
    rng = np.random.default_rng(seed)
    if mix["kind"] == "open_loop":
        segs = segments(mix, seconds)
    else:
        n, depth = mix["requests"], mix["queue_depth"]
        segs = [(0.0, 0.0, min(depth, n - i)) for i in range(0, n, depth)]
    prompts, outs, due = [], [], []
    for t0, dur, n in segs:
        if n == 0:
            continue
        prompts.append(rng.permutation(stratified_lognormal(n, mix["prompt"])))
        outs.append(rng.permutation(stratified_lognormal(n, mix["output"])))
        if dur > 0:
            # n Poisson arrivals given their count: n + 1 exponential gaps
            # scaled to fill the segment, so each lies strictly inside it
            g = rng.permutation(stratified_gaps(n + 1, 1.0))
            due.append(t0 + dur * np.cumsum(g)[:-1] / g.sum())
        else:
            due.append(np.full(n, t0))
    prompts, outs, due = (np.concatenate(x) for x in (prompts, outs, due))
    toks = rng.integers(1, vocab, int(prompts.sum()))
    reqs, at = [], 0
    for i in range(len(prompts)):
        p = int(prompts[i])
        reqs.append(Request(i, float(due[i]), toks[at:at + p].tolist(),
                            int(outs[i])))
        at += p
    return reqs


def train_batches(vocab: int, batch: int, seq: int, seed: int):
    """Endless token batches (B, S) from the seed, every row different:
    noisy arithmetic progressions mod the vocabulary, so the loss has
    structure to fall along and the gradient is not noise alone."""
    rng = np.random.default_rng(seed)
    pos = np.arange(seq)[None, :]
    while True:
        base = rng.integers(0, vocab, (batch, 1))
        stride = rng.integers(1, 4, (batch, 1))
        toks = (base + stride * pos) % vocab
        noise = rng.random((batch, seq)) < 0.05
        toks = np.where(noise, rng.integers(0, vocab, toks.shape), toks)
        yield toks.astype(np.int32)
