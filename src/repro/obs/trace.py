"""Host-side tracing: nestable spans exported as Chrome trace-event JSON.

The runtime lens the MXNet paper's systems story needs (and the
TensorFlow whitepaper ships as EEG): the dependency engine's waves, the
trainer's data-wait/step/checkpoint cadence and the serving engine's
per-request lifecycle and step phases all record onto one timeline that
Perfetto / ``chrome://tracing`` opens directly (DESIGN.md §11).

Design constraints:

* **~zero overhead when disabled** — the common case.  ``span()`` on a
  disabled recorder returns a shared ``nullcontext`` (no allocation, one
  attribute check); ``instant``/``counter``/``complete`` return
  immediately, and no GC callback is installed.
* **one clock with the device trace** — while enabled, every ``span()``
  also enters a ``jax.profiler.TraceAnnotation`` of the same name, so a
  ``jax.profiler`` trace holds the span on its host plane, on the device
  ops' clock.  Events recorded after the fact (``complete``, compile and
  GC spans) stay on this recorder's clock.
* **compile and GC spans** — between ``enable(True)`` and
  ``enable(False)`` every ``/jax/core/compile/*`` event becomes a
  ``compile`` span (track ``jit``, with its ``stage`` and ``fun_name``)
  and every garbage collection a ``gc`` span (track ``gc``, with its
  ``generation``).
* **thread-safe** — the engine executes ops from waiter threads and the
  data pipeline prefetches on background threads; events append under a
  reentrant lock (a GC callback may fire while the lock is held), and
  each thread's events land on its own track by default.
* **dependency-free** — stdlib only; jax is imported lazily, and only
  while tracing.

Event model (Chrome trace-event format, the subset Perfetto renders):

* ``ph: "X"`` complete events — spans with ``ts``/``dur`` in µs;
* ``ph: "i"`` instant events — points in time (request milestones);
* ``ph: "C"`` counter events — numeric tracks (block-pool occupancy);
* ``ph: "M"`` metadata — human-readable track names, emitted at export.

Tracks are logical names ("engine", "trainer", "serve", "req3"), mapped
to stable ``tid`` ints at first use; ``pid`` is always 1 (one host
process — device timelines come from ``jax.profiler``, not from this
recorder).

Worked example (pure host tracing — runs anywhere)::

    >>> rec = TraceRecorder(enabled=True)
    >>> with rec.span("outer", cat="demo"):
    ...     with rec.span("inner", cat="demo") as args:
    ...         args["n"] = 2                   # args known at the end
    ...         rec.instant("tick", cat="demo")
    >>> [e["name"] for e in rec.events()]       # inner closes first
    ['tick', 'inner', 'outer']
    >>> rec.events()[1]["args"]
    {'n': 2}
    >>> doc = rec.export()
    >>> sorted(doc) == ['displayTimeUnit', 'traceEvents']
    True
"""
from __future__ import annotations

import contextlib
import gc
import json
import threading
import time
from contextlib import nullcontext

_NULL = nullcontext()


def _coerce(o):
    """JSON fallback for span-arg payloads: numpy/jax scalars carry
    ``__int__``/``__float__``; anything else degrades to its repr rather
    than corrupting the export mid-write."""
    for cast in (int, float):
        try:
            return cast(o)
        except (TypeError, ValueError):
            continue
    return str(o)


class TraceRecorder:
    """Thread-safe span/instant/counter recorder with Perfetto export."""

    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self._events: list[dict] = []
        self._lock = threading.RLock()
        self._t0 = time.perf_counter()
        self._tracks: dict[str, int] = {}
        self._tls = threading.local()

    # -- time / track bookkeeping ------------------------------------------
    def now_us(self) -> float:
        """Microseconds since this recorder's epoch."""
        return (time.perf_counter() - self._t0) * 1e6

    def to_us(self, t_perf: float) -> float:
        """Convert a raw ``time.perf_counter()`` stamp to recorder µs —
        for lifecycle events whose begin was stamped before the event is
        recorded (e.g. a request's enqueue time)."""
        return (t_perf - self._t0) * 1e6

    def _tid(self, track: str | None) -> int:
        if track is None:
            track = getattr(self._tls, "name", None)
            if track is None:
                track = threading.current_thread().name
        tid = self._tracks.get(track)
        if tid is None:
            tid = self._tracks[track] = len(self._tracks) + 1
        return tid

    def set_thread_track(self, name: str) -> None:
        """Default track for events recorded from the calling thread."""
        self._tls.name = name

    # -- recording ---------------------------------------------------------
    @contextlib.contextmanager
    def _span(self, name, cat, track, args):
        with _profiler_annotation(name):
            t0 = self.now_us()
            try:
                yield args
            finally:
                t1 = self.now_us()
                ev = {"name": name, "cat": cat, "ph": "X", "ts": t0,
                      "dur": t1 - t0, "pid": 1}
                if args:
                    ev["args"] = args
                with self._lock:
                    ev["tid"] = self._tid(track)
                    self._events.append(ev)

    def span(self, name: str, cat: str = "host", track: str | None = None,
             **args):
        """Context manager recording one complete event around its body,
        and a ``jax.profiler.TraceAnnotation`` of the same name.  The
        ``with`` binds the event's args dict, so the body can add args
        it learns on the way.

        Disabled recorders return a shared ``nullcontext`` (which binds
        None) — the hot-path cost of an un-traced span is one attribute
        check.
        """
        if not self.enabled:
            return _NULL
        return self._span(name, cat, track, args)

    def complete(self, name: str, start_us: float, end_us: float,
                 cat: str = "host", track: str | None = None, **args):
        """Record a span whose begin/end happened in different call frames
        (e.g. a request's queued->admitted interval)."""
        if not self.enabled:
            return
        ev = {"name": name, "cat": cat, "ph": "X", "ts": start_us,
              "dur": max(end_us - start_us, 0.0), "pid": 1}
        if args:
            ev["args"] = args
        with self._lock:
            ev["tid"] = self._tid(track)
            self._events.append(ev)

    def instant(self, name: str, cat: str = "host",
                track: str | None = None, **args):
        if not self.enabled:
            return
        ev = {"name": name, "cat": cat, "ph": "i", "ts": self.now_us(),
              "s": "t", "pid": 1}
        if args:
            ev["args"] = args
        with self._lock:
            ev["tid"] = self._tid(track)
            self._events.append(ev)

    def counter(self, name: str, value, track: str | None = None,
                cat: str = "host"):
        """Counter-track sample (rendered as a filled line in Perfetto)."""
        if not self.enabled:
            return
        ev = {"name": name, "cat": cat, "ph": "C", "ts": self.now_us(),
              "pid": 1, "args": {"value": value}}
        with self._lock:
            ev["tid"] = self._tid(track)
            self._events.append(ev)

    # -- export ------------------------------------------------------------
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def export(self, path: str | None = None) -> dict:
        """Chrome trace-event / Perfetto JSON document; writes ``path``
        when given.  Track-name metadata events come first so Perfetto
        labels every row."""
        with self._lock:
            events = list(self._events)
            tracks = dict(self._tracks)
        meta = [{"name": "process_name", "ph": "M", "pid": 1,
                 "args": {"name": "repro"}}]
        for name, tid in sorted(tracks.items(), key=lambda kv: kv[1]):
            meta.append({"name": "thread_name", "ph": "M", "pid": 1,
                         "tid": tid, "args": {"name": name}})
        doc = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f, default=_coerce)
        return doc


# ---------------------------------------------------------------------------
# module-level default recorder (what the instrumented layers talk to)

_RECORDER = TraceRecorder(enabled=False)
# the recorder enable() switched on: compile and GC spans go to it
_WATCHED: TraceRecorder | None = None
_LISTENING = False          # the jax.monitoring listener is registered
_GC_START = 0.0             # perf_counter() at the running collection's start

COMPILE_EVENT = "/jax/core/compile/"


def get_recorder() -> TraceRecorder:
    return _RECORDER


def set_recorder(rec: TraceRecorder) -> TraceRecorder:
    """Install ``rec`` as the default recorder.  Compile and GC spans
    stop: only ``enable()`` starts them."""
    global _RECORDER
    _RECORDER = rec
    _watch(None)
    return _RECORDER


def enable(enabled: bool = True) -> TraceRecorder:
    """Turn the default recorder on/off (fresh event buffer when enabling
    from off, so a CLI's --trace starts a clean timeline).  On, it also
    records a ``compile`` span per JAX compile stage and a ``gc`` span
    per garbage collection; off, the GC callback is removed."""
    global _RECORDER
    if enabled and not _RECORDER.enabled:
        _RECORDER = TraceRecorder(enabled=True)
    else:
        _RECORDER.enabled = enabled
    _watch(_RECORDER if enabled else None)
    return _RECORDER


def _watch(rec: TraceRecorder | None) -> None:
    """Send compile and GC spans to ``rec`` (None: nowhere).  The JAX
    listener is registered once and returns at once while nothing is
    watched; the GC callback is installed only while something is."""
    global _WATCHED, _LISTENING
    _WATCHED = rec
    if rec is None:
        if _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)
        return
    if not _LISTENING:
        try:
            from jax import monitoring
        except ImportError:     # jax absent: GC spans only
            pass
        else:
            monitoring.register_event_time_span_listener(_on_jax_event)
            _LISTENING = True
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def _on_jax_event(event: str, start_s: float, end_s: float, **kw):
    """``jax.monitoring`` time-span listener: a compile stage (wall-clock
    seconds) becomes a ``compile`` span on the ``jit`` track."""
    rec = _WATCHED
    if rec is None or not rec.enabled or not event.startswith(COMPILE_EVENT):
        return
    shift = time.perf_counter() - time.time()
    rec.complete("compile", rec.to_us(start_s + shift),
                 rec.to_us(end_s + shift), cat="jit", track="jit",
                 stage=event[len(COMPILE_EVENT):],
                 fun_name=str(kw.get("fun_name", "")))


def _on_gc(phase: str, info: dict):
    """``gc.callbacks`` hook: one ``gc`` span per collection."""
    global _GC_START
    if phase == "start":
        _GC_START = time.perf_counter()
        return
    rec = _WATCHED
    if rec is not None and rec.enabled:
        rec.complete("gc", rec.to_us(_GC_START), rec.now_us(), cat="gc",
                     track="gc", generation=info.get("generation"))


def tracing() -> bool:
    return _RECORDER.enabled


def span(name: str, cat: str = "host", track: str | None = None, **args):
    return _RECORDER.span(name, cat=cat, track=track, **args)


def instant(name: str, cat: str = "host", track: str | None = None, **args):
    return _RECORDER.instant(name, cat=cat, track=track, **args)


def export(path: str | None = None) -> dict:
    return _RECORDER.export(path)


# ---------------------------------------------------------------------------
# device-profile alignment (jax.profiler / HLO metadata)

def _profiler_annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)``: the span on a profiler
    trace's host plane.  Called only while tracing."""
    try:
        from jax import profiler
    except ImportError:     # jax absent: host-side span only
        return _NULL
    return profiler.TraceAnnotation(name)


def named_scope(name: str):
    """Name the ops traced inside the body (HLO op-metadata scope), so a
    device profile (``jax.profiler.trace``) shows the same ring-step /
    pipeline-tick / bucket-chain names as the host timeline.  Also records
    a host span on the default recorder when tracing is enabled — jit
    tracing happens once, so these spans show the *trace-time* structure
    (which scheduled region was being staged), not per-execution timing.
    """
    try:
        import jax
        scope = jax.named_scope(name)
    except Exception:   # jax absent/ancient: host-side span only
        scope = _NULL
    if not _RECORDER.enabled:
        return scope
    stack = contextlib.ExitStack()
    stack.enter_context(_RECORDER.span(name, cat="jit-trace",
                                       track="jit-trace"))
    stack.enter_context(scope)
    return stack
