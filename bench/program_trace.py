"""Readers of the serving engine's and the recorder's own spans
(``repro.obs``), shared by the per-layer metric files that read them.

Two kinds of span reach a traced run.  Spans the program opens as
context managers (``engine_step`` and its phases) are also
``jax.profiler.TraceAnnotation`` s, so they lie on the trace's host plane
on the device ops' clock.  Spans recorded after the fact (``compile``,
``gc``) are on the recorder's clock only, and are mapped onto the
trace's with the offset the harness found for the window.  A program
that records none of them (one older than these spans) reads as nothing:
every reader here returns None then.
"""
from __future__ import annotations

import bisect

from . import trace_reduce

STEP = "engine_step"
# the phases of PagedServeEngine.step, nested as the engine opens them
PHASES = ("admit", "prefill_build", "prefill_chunk", "prefill_dispatch",
          "prefill_wait", "first_token", "batch_build", "decode_step",
          "decode_dispatch", "decode_sync", "retire")
AFTER_THE_FACT = ("compile", "gc")
# the stage of a ``compile`` that is the XLA compile itself
BACKEND_COMPILE = "backend_compile_duration"
# program spans that reach the trace's host plane only from a program
# that also records compile spans (the serving step; the trainer's data
# wait, each step)
LISTENER_ERA = (STEP, "data_wait")
UNATTRIBUTED = "unattributed"


def offset_ns(ctx) -> float:
    """Trace time minus host ``perf_counter`` time, in ns."""
    return ctx.dev_window[0] - ctx.window[0] * 1e9


def engine_steps(ctx):
    """The window's ``engine_step`` spans on the trace's host plane,
    (start_ns, end_ns), sorted."""
    t0, t1 = ctx.dev_window
    return sorted((s, e) for s, e, n in ctx.trace.host
                  if n == STEP and s >= t0 and e <= t1)


def labels(ctx):
    """Every span that can name what the host did in an idle gap: the
    phases from the host plane, compile and GC spans mapped from the
    recorder; (start_ns, end_ns, name) sorted by start."""
    off = offset_ns(ctx)
    out = [(s, e, n) for s, e, n in ctx.trace.host if n in PHASES]
    out += [(a * 1e9 + off, b * 1e9 + off, n) for a, b, n, _ in ctx.spans
            if n in AFTER_THE_FACT]
    out.sort()
    return out


def _within(intervals, starts, s, e, widest):
    """The intervals (sorted by start, none longer than ``widest``) that
    overlap [s, e]."""
    i = bisect.bisect_left(starts, s - widest)
    j = bisect.bisect_right(starts, e)
    return [iv for iv in intervals[i:j] if iv[1] > s and iv[0] < e]


def attribute(gaps, spans) -> dict:
    """Split each gap (start_ns, end_ns) among the spans open in it: each
    piece between span edges goes to the shortest span open there (the
    innermost), or to ``unattributed``.  Returns ns per name."""
    out: dict = {}
    for gs, ge in gaps:
        open_ = [(s, e, n) for s, e, n in spans if e > gs and s < ge]
        edges = sorted({gs, ge} | {t for s, e, _ in open_ for t in (s, e)
                                   if gs < t < ge})
        for a, b in zip(edges, edges[1:]):
            mid = (a + b) / 2
            inner = [(e - s, n) for s, e, n in open_ if s <= mid <= e]
            name = min(inner)[1] if inner else UNATTRIBUTED
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def sched_idle_ms(ctx):
    """Device-0 idle time inside the window's ``engine_step`` spans, per
    step, in ms; ``by_phase`` splits it by the innermost program span
    open (a phase, ``compile``, ``gc`` or ``unattributed``)."""
    steps = engine_steps(ctx)
    if not steps or not ctx.trace.devices:
        return None
    busy = ctx.trace.devices[0].busy
    b_starts = [s for s, _ in busy]
    b_widest = max((e - s for s, e in busy), default=0.0)
    spans = labels(ctx)
    l_starts = [s for s, _, _ in spans]
    l_widest = max((e - s for s, e, _ in spans), default=0.0)
    by: dict = {}
    for s, e in steps:
        held = trace_reduce.clip(_within(busy, b_starts, s, e, b_widest),
                                 s, e)
        gaps = trace_reduce.subtract([(s, e)], held)
        for name, ns in attribute(
                gaps, _within(spans, l_starts, s, e, l_widest)).items():
            by[name] = by.get(name, 0.0) + ns
    n = len(steps)
    return {"value": sum(by.values()) / 1e6 / n,
            "by_phase": {k: v / 1e6 / n for k, v in
                         sorted(by.items(), key=lambda kv: -kv[1])},
            "steps": n}


def step_counts(ctx):
    """The args of the window's ``engine_step`` spans (the recorder's
    counts of each step's work)."""
    w0, w1 = ctx.window
    return [a for s, e, n, a in ctx.spans
            if n == STEP and s >= w0 and e <= w1 and "lanes" in a]


def mean_count(ctx, key: str, decoding_only: bool = False):
    """Mean of one ``engine_step`` count over the window's steps (only
    those that decoded, if asked)."""
    rows = step_counts(ctx)
    if decoding_only:
        rows = [a for a in rows if a["lanes"] > 0]
    if not rows:
        return None
    return {"value": sum(a[key] for a in rows) / len(rows),
            "steps": len(rows)}


def window_compiles(ctx):
    """XLA compiles that overlap the window (one ``backend_compile``
    stage each; a compile's other stages, tracing and lowering, count
    only in ``by_phase``), with the names of the functions compiled.
    None where the program records no compile spans at all, which the
    window tells by holding none of the spans (``LISTENER_ERA``) that
    only such a program puts on the trace's host plane; 0 where it does
    and no compile fell in the window."""
    t0, t1 = ctx.dev_window
    if not any(n in LISTENER_ERA and s >= t0 and e <= t1
               for s, e, n in ctx.trace.host):
        return None
    w0, w1 = ctx.window
    hits = [a for s, e, n, a in ctx.spans
            if n == "compile" and a.get("stage") == BACKEND_COMPILE
            and e > w0 and s < w1]
    return {"value": len(hits),
            "fun_names": sorted({a.get("fun_name", "") for a in hits})}
