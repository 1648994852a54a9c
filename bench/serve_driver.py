"""Drivers of the serving mixes: ``open_loop`` (requests due on a Poisson
schedule, timed from when each was due) and ``backlog`` (every request
due at the start, a queue deeper than the window can drain).  Both drive
``PagedServeEngine.add_request`` and ``PagedServeEngine.step`` and read
every token as the step that emitted it returns.
"""
from __future__ import annotations

import gc
from dataclasses import dataclass, field

import numpy as np

from . import common, traffic


@dataclass
class Rec:
    """What the client saw of one request."""
    idx: int
    due: float                 # host clock, absolute
    prompt_len: int
    t_add: float = 0.0
    t_first: float | None = None
    t_last: float | None = None
    n: int = 0                 # tokens seen so far
    done: bool = False
    ok: bool = False
    tokens: list = field(default_factory=list)


@dataclass
class StepRec:
    """One ``engine.step()``: its host interval, the prefill rows it ran
    as (start, n) spans and the context length of every decode token."""
    t0: float
    t1: float
    prefill: list
    decode_ctx: list


class Tracker:
    """Follows every admitted request through the engine and stamps each
    token with the end of the step that emitted it.  ``gaps`` holds, for
    every token after a request's first, (when it came, the time since
    that request's previous token)."""

    def __init__(self, engine, clock, annotate: bool = False):
        self.engine = engine
        self.clock = clock
        self.annotate = annotate
        self.recs: dict[int, Rec] = {}
        self.live: dict[int, tuple] = {}     # idx -> (engine request, Rec)
        self.steps: list[StepRec] = []
        self.emitted: list[tuple[float, int]] = []
        self.gaps: list[tuple[float, float]] = []

    def add(self, req: traffic.Request, due_abs: float) -> None:
        rec = Rec(req.idx, due_abs, len(req.prompt), t_add=self.clock.now())
        self.recs[req.idx] = rec
        ticket = self.engine.add_request(req.prompt, req.max_new)
        if not ticket.accepted:
            rec.done = True
            return
        queued = next(r for r in self.engine.pending if r.rid == ticket.rid)
        self.live[req.idx] = (queued, rec)

    def step(self) -> None:
        before = {i: (r.prefilled, len(r.out)) for i, (r, _) in
                  self.live.items()}
        if self.annotate:
            from jax.profiler import TraceAnnotation
            with TraceAnnotation("bench_step"):
                t0 = self.clock.now()
                self.engine.step()
                t1 = self.clock.now()
        else:
            t0 = self.clock.now()
            self.engine.step()
            t1 = self.clock.now()
        prefill, ctx, total = [], [], 0
        results = self.engine.results
        for i, (r, rec) in list(self.live.items()):
            p0, n0 = before[i]
            if r.prefilled > p0:
                prefill.append((p0, r.prefilled - p0))
            n1 = len(r.out)
            if n1 > n0:
                if n0 == 0:
                    rec.t_first = t1
                # tokens after the first of one step came with no wait
                self.gaps.extend((t1, t1 - rec.t_last if j == n0 else 0.0)
                                 for j in range(max(n0, 1), n1))
                # output j >= 1 was decoded from input j-1 at position
                # prompt + j - 1, attending over prompt + j tokens
                ctx.extend(rec.prompt_len + j for j in range(max(n0, 1), n1))
                rec.t_last, rec.n = t1, n1
                total += n1 - n0
            if r.rid in results:
                res = results[r.rid]
                rec.done, rec.ok = True, res.status.name == "OK"
                rec.tokens = list(res.tokens)
                del self.live[i]
        self.steps.append(StepRec(t0, t1, prefill, ctx))
        self.emitted.append((t1, total))


def build_engine(cfg, conf, mix, params, seed):
    """The engine as the configuration states it, its sampling set, and
    its programs warmed on one throwaway request of the cell's own
    shapes."""
    from repro.serve import PagedServeEngine
    eng = {**conf["engine"], **mix.get("engine", {})}
    samp = mix["sampling"]
    engine = PagedServeEngine(
        cfg, params, block_size=eng["block_size"],
        max_batch=eng["max_batch"], max_len=eng["max_len"],
        prefill_chunk=eng["prefill_chunk"],
        num_blocks=conf["kv_pool"]["num_blocks"],
        top_k=samp.get("top_k"), top_p=samp.get("top_p"))
    # sets the sampling temperature and key from the seed; no request
    engine.generate([], temperature=samp.get("temperature", 0.0),
                    seed=seed & 0x7FFFFFFF, warmup=False)
    engine.warmup()
    return engine, eng


def _pct(vals, q):
    return float(np.percentile(np.asarray(vals, float), q)) if vals else None


def sample_for_check(recs, rng, want_tokens: int):
    """Finished requests drawn from the seed, the longest among them,
    until they hold ``want_tokens`` served tokens."""
    done = [r for r in recs if r.ok and r.tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), r.idx))
    pick, total = [longest], len(longest.tokens)
    for j in rng.permutation(len(done)):
        if total >= want_tokens:
            break
        r = done[j]
        if r is not longest:
            pick.append(r)
            total += len(r.tokens)
    return pick


def run_open_loop(engine, reqs, mix, seconds, clock, window: common.Window,
                  step_hook=None):
    """Offer ``reqs`` at their due times; the window is the ``seconds``
    after ``warm_s``, and the run ends with it.  Returns the tracker, the
    requests due in the window and how late each was added."""
    tr = Tracker(engine, clock, annotate=window.trace_dir is not None)
    start = clock.now()
    w0, w1 = start + mix["warm_s"], start + mix["warm_s"] + seconds
    late, i, n = [], 0, len(reqs)
    in_window = [r.idx for r in reqs if w0 <= start + r.due < w1]
    opened = False
    while True:
        now = clock.now()
        if not opened and now >= w0:
            window.open()
            opened = True
        if opened and now >= w1:
            window.close()
            break
        while i < n and start + reqs[i].due <= now:
            tr.add(reqs[i], start + reqs[i].due)
            late.append(now - (start + reqs[i].due))
            i += 1
        if engine.busy:
            if step_hook:
                step_hook()
            tr.step()
        else:
            # idle: wait for the next arrival or the window's next edge
            edge = w1 if opened else w0
            due = start + reqs[i].due if i < n else edge
            clock.sleep(max(0.0, min(due, edge) - clock.now()))
    return tr, in_window, late


def run_backlog(engine, reqs, mix, seconds, clock, window: common.Window,
                max_batch: int, fill_limit_s=240.0):
    """Every request is due at once; the harness keeps the engine's queue
    ``queue_depth`` deep from the backlog.  The window opens once the
    lanes have filled: every lane holds a request and ``max_batch``
    requests have finished, so the first cohort, admitted together, has
    turned over once."""
    tr = Tracker(engine, clock, annotate=window.trace_dir is not None)
    start = clock.now()
    depth = mix.get("queue_depth", 2 * max_batch)
    i, n = 0, len(reqs)
    while True:
        while i < n and len(engine.pending) < depth:
            tr.add(reqs[i], start)
            i += 1
        now = clock.now()
        if window.t0 is None:
            done = sum(1 for r in tr.recs.values() if r.done)
            if all(s is not None for s in engine.slots) and done >= max_batch:
                window.open()
            elif now - start > fill_limit_s:
                raise common.BenchError("backlog lanes never filled")
        elif now >= window.t0 + seconds:
            window.close()
            break
        if i >= n and not engine.pending and window.t0 is not None:
            raise common.BenchError("the backlog drained inside the window")
        tr.step()
    return tr, i


def serve_metrics(tr: Tracker, window: common.Window, in_window, kind):
    """End-to-end metrics on the host clock.  ``tpot_p95_ms`` is the 95th
    percentile, over every output token the window emitted (each
    request's first excepted), of the time since that request's previous
    token: all requests running in the window, thousands of tokens."""
    w0, w1 = window.t0, window.t1
    out = {}
    toks = sum(n for t, n in tr.emitted if w0 < t <= w1)
    out["output_tokens_per_s"] = toks / (w1 - w0)
    if kind == "open_loop":
        out["tpot_p95_ms"] = _pct([1e3 * g for t, g in tr.gaps
                                   if w0 < t <= w1], 95)
        # a request still running when the window closes is late, not
        # lost; one the engine refused or ended badly is failed
        recs = [tr.recs[j] for j in in_window if j in tr.recs]
        failed = (len(in_window) - len(recs)
                  + sum(1 for r in recs if r.done and not r.ok))
        attempted = len(in_window)
    else:
        started = [r for r in tr.recs.values() if r.t_add <= w1]
        attempted = len(started)
        failed = sum(1 for r in started if r.done and not r.ok)
    return out, attempted, failed


def drive(cell, cfg, seed: int, seconds: float, trace_dir, clock,
          t_proc0: float, fault=None, control=False):
    """Set up, run the window, read memory, free the engine, check against
    the reference.  Returns a dict the entry point turns into the result
    line."""
    import jax
    from .reference import check
    conf, mix, ref, d = cell.config, cell.mix, cell.reference, cell.dims
    params = common.make_params(cfg, seed, ref, d)
    engine, eng = build_engine(cfg, conf, mix, params, seed)
    if fault is not None:
        fault(engine)
    reqs = traffic.make_requests(mix, cfg.vocab, seed, seconds)
    window = common.Window(clock, trace_dir)
    if mix["kind"] == "open_loop":
        tr, in_window, late = run_open_loop(engine, reqs, mix, seconds,
                                            clock, window)
        common.log(open_loop_late_ms={"p50": _pct(late, 50) * 1e3,
                                      "p95": _pct(late, 95) * 1e3,
                                      "n": len(late)},
                   requests_due_in_window=len(in_window))
    else:
        tr, _ = run_backlog(engine, reqs, mix, seconds, clock, window,
                            eng["max_batch"])
        in_window = []
    setup_s = window.t0 - t_proc0
    metrics, attempted, failed = serve_metrics(tr, window, in_window,
                                               mix["kind"])
    metrics["setup_s"] = setup_s
    common.log(window_metrics=metrics)
    mem = common.memory_peak_bytes(jax.devices()[:cell.chips])
    spans = None
    if trace_dir is not None:
        spans = common.program_spans()
    steps = tr.steps
    recs = list(tr.recs.values())
    del engine, tr
    gc.collect()

    # the check: a sample of the requests the window finished, against
    # the reference, once the program's state is freed
    chk = mix["check"]
    rng = np.random.default_rng(seed ^ 0x5EED)
    finished = [r for r in recs if r.done and r.t_last is not None
                and window.t0 < r.t_last <= window.t1]
    picked = sample_for_check(finished, rng, chk["tokens"])
    by_idx = {r.idx: r for r in reqs}
    samples = [(by_idx[r.idx].prompt, r.tokens) for r in picked]
    res = check.serve_gaps(ref, params, samples, d, mix["sampling"],
                           max_out=mix["output"]["max"], seed=seed,
                           control=control)
    # the limits file names the numbers compared; every gap is logged
    common.log(check_detail=res)
    checks = {k: {"value": res["program"][k], "limit": v}
              for k, v in cell.limits.items()}
    checks["checked_tokens"] = {"value": res["tokens"], "limit": chk["tokens"]}
    enough = bool(samples) and res["tokens"] >= chk["tokens"]
    return {"metrics": metrics, "attempted": attempted, "failed": failed,
            "memory_peak_bytes": mem, "checks": checks,
            "correct": enough and common.within(res["program"], cell.limits),
            "window": (window.t0, window.t1), "steps": steps,
            "spans": spans,
            "readings": dict(res["program"], checked_tokens=res["tokens"]),
            "control": res["control"],
            "control_correct": (None if not control else enough
                                and common.within(res["control"],
                                                  cell.limits))}
