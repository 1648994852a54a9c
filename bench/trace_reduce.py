"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read, all on one clock:

* per device: busy intervals (the union of the intervals in which an
  operation ran), device time per op name, and collective time exposed
  against compute;
* the host's annotations (``jax.profiler.TraceAnnotation``), and from
  them the offset that maps the harness's host clock onto the trace's;
* idle gaps inside the window, each labelled by the innermost host span
  open at the gap's middle.

It reads the file with ``jax.profiler.ProfileData`` and nothing of the
program.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

COLLECTIVE_MARKS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all", "allreduce",
                    "all_reduce")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class DeviceOps:
    """One device's operations and compiled programs (modules), each
    (start_ns, end_ns, name) sorted by start.  An op may contain others
    (a ``while`` holds its body's ops)."""
    name: str
    events: list
    modules: list = field(default_factory=list)
    busy: list = field(default_factory=list)      # merged intervals


@dataclass
class Trace:
    devices: list                 # [DeviceOps]
    host: list                    # [(start_ns, end_ns, name)]


def find_xplane(trace_dir) -> str:
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def merge(intervals):
    """Union of (start, end) intervals, sorted and merged."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b):
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def short_name(name: str) -> str:
    """An HLO op's instruction name: ``%copy.75 = bf16[...] copy(...)``
    becomes ``copy.75``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line, short=False):
    return [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
             short_name(ev.name) if short else ev.name)
            for ev in line.events]


def load(path_or_dir) -> Trace:
    """Device op lines and host annotation lines of a trace."""
    from jax.profiler import ProfileData
    path = str(path_or_dir)
    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:") and OPS_LINE in lines:
            evs = sorted(_events(lines[OPS_LINE], short=True))
            mods = (sorted(_events(lines[MODULES_LINE]))
                    if MODULES_LINE in lines else [])
            devices.append(DeviceOps(plane.name, evs, mods,
                                     merge((s, e) for s, e, _ in evs)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(_events(ln))
    devices.sort(key=lambda d: d.name)
    host.sort()
    return Trace(devices, host)


def clock_offset_ns(trace: Trace, name: str, host_starts) -> float:
    """Trace time minus host ``perf_counter`` time (in ns), from the
    annotations named ``name`` whose host start times the harness kept,
    paired in order (median of the differences)."""
    ann = [s for s, _, n in trace.host if n == name]
    k = min(len(ann), len(host_starts))
    if k == 0:
        raise ValueError(f"no {name!r} annotation in the trace")
    diffs = sorted(a - h * 1e9 for a, h in zip(ann[:k], host_starts[:k]))
    return diffs[k // 2]


def busy_s(trace: Trace, t0, t1) -> float:
    """Device-busy seconds in [t0, t1] (ns), averaged over devices."""
    if not trace.devices:
        return 0.0
    return sum(total(clip(d.busy, t0, t1)) for d in trace.devices) \
        / len(trace.devices) / 1e9


def op_seconds(trace: Trace, t0, t1, match=None, device: int | None = None):
    """Seconds per op name inside [t0, t1], summed over the chosen
    devices; ``match(name)`` keeps only some ops."""
    out = {}
    devs = trace.devices if device is None else trace.devices[device:device + 1]
    for d in devs:
        for s, e, n in d.events:
            if e <= t0 or s >= t1 or (match and not match(n)):
                continue
            out[n] = out.get(n, 0.0) + (min(e, t1) - max(s, t0)) / 1e9
    return out


def is_collective(name: str) -> bool:
    low = name.lower()
    return any(m in low for m in COLLECTIVE_MARKS)


def leaves(events):
    """The events that hold no other event (a loop's body ops, not the
    loop).  ``events`` sorted by start."""
    out = []
    order = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    for i, (s, e, n) in enumerate(order):
        nxt = order[i + 1][0] if i + 1 < len(order) else e
        if nxt >= e:
            out.append((s, e, n))
    return out


def exposed_collective_s(trace: Trace, t0, t1) -> float:
    """Collective time during which no compute op runs on that device,
    averaged over devices.  Compute is the leaf ops: a loop that holds a
    collective does not hide it."""
    if not trace.devices:
        return 0.0
    tot = 0.0
    for d in trace.devices:
        leaf = leaves(d.events)
        coll = merge((s, e) for s, e, n in leaf if is_collective(n))
        comp = merge((s, e) for s, e, n in leaf if not is_collective(n))
        tot += total(clip(subtract(coll, comp), t0, t1))
    return tot / len(trace.devices) / 1e9


def idle_gaps(trace: Trace, t0, t1, spans, top=10):
    """The longest idle gaps of device 0 in [t0, t1], each labelled by the
    innermost of ``spans`` ((start_ns, end_ns, name) on the trace's clock)
    open at its middle."""
    if not trace.devices:
        return []
    busy = clip(trace.devices[0].busy, t0, t1)
    gaps = subtract([(t0, t1)], busy)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        open_ = [(b - a, n) for a, b, n in spans if a <= mid <= b]
        label = min(open_)[1] if open_ else "outside any host span"
        out.append([label, (e - s) / 1e9])
    return out


def op_kind(name: str) -> str:
    """``copy.75`` -> ``copy``: instances of one op kind grouped."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def self_seconds(trace: Trace, t0, t1):
    """Seconds per op kind inside [t0, t1], each op counted for the time
    no op nested inside it runs (a loop's own time, not its body's),
    averaged over devices."""
    out = {}
    for d in trace.devices:
        evs = [(max(s, t0), min(e, t1), n) for s, e, n in d.events
               if e > t0 and s < t1]
        children = [[] for _ in evs]
        stack = []
        for i, (s, e, n) in enumerate(evs):
            while stack and evs[stack[-1]][1] <= s:
                stack.pop()
            if stack:
                children[stack[-1]].append((s, min(e, evs[stack[-1]][1])))
            stack.append(i)
        for (s, e, n), ch in zip(evs, children):
            own = (e - s) - total(merge(ch))
            k = op_kind(n)
            out[k] = out.get(k, 0.0) + own / 1e9
    n = max(len(trace.devices), 1)
    return {k: v / n for k, v in out.items()}


def module_seconds(trace: Trace, t0, t1, mark: str, device: int = 0):
    """Device seconds of the compiled programs whose name holds ``mark``."""
    if len(trace.devices) <= device:
        return 0.0
    return sum((min(e, t1) - max(s, t0)) / 1e9
               for s, e, n in trace.devices[device].modules
               if mark in n and e > t0 and s < t1)


def top_ops(trace: Trace, t0, t1, top=10):
    secs = self_seconds(trace, t0, t1)
    return [[k, v] for k, v in
            sorted(secs.items(), key=lambda kv: -kv[1])[:top]]
