"""Observability layer (DESIGN.md §11): trace recorder semantics,
metrics quantiles, Perfetto export validity, the serving engine's
per-request lifecycle spans and step phases, compile and GC spans, the
profiler's copy of each span, and engine-stats reset coherence."""
import gc
import json

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.engine import Tag, reset_default_engine
from repro.models import get_model, reduced
from repro.obs import (Metrics, TraceRecorder, get_metrics, get_recorder,
                       set_recorder)
from repro.serve import PagedServeEngine

KEY = jax.random.PRNGKey(0)


@pytest.fixture
def recorder():
    """Fresh enabled recorder installed as the process default."""
    old = get_recorder()
    rec = set_recorder(TraceRecorder(enabled=True))
    yield rec
    set_recorder(old)


# ---------------------------------------------------------------------------
# trace recorder

def test_span_nesting_and_ordering(recorder):
    with recorder.span("outer", cat="t"):
        with recorder.span("inner", cat="t"):
            recorder.instant("mark", cat="t")
    names = [e["name"] for e in recorder.events()]
    assert names == ["mark", "inner", "outer"]      # inner closes first
    by = {e["name"]: e for e in recorder.events()}
    # the outer interval contains the inner one
    assert by["outer"]["ts"] <= by["inner"]["ts"]
    assert (by["inner"]["ts"] + by["inner"]["dur"]
            <= by["outer"]["ts"] + by["outer"]["dur"] + 1e-6)
    assert by["mark"]["ph"] == "i"


def test_disabled_recorder_records_nothing():
    rec = TraceRecorder(enabled=False)
    with rec.span("a"):
        rec.instant("b")
        rec.counter("c", 1)
    rec.complete("d", 0.0, 1.0)
    assert rec.events() == []
    # the disabled span path allocates nothing: one shared nullcontext
    assert rec.span("x") is rec.span("y")


def test_tracks_map_to_stable_tids(recorder):
    with recorder.span("a", track="engine"):
        pass
    with recorder.span("b", track="serve"):
        pass
    with recorder.span("c", track="engine"):
        pass
    by = {e["name"]: e["tid"] for e in recorder.events()}
    assert by["a"] == by["c"] != by["b"]


def test_cross_frame_complete_event(recorder):
    import time
    t0 = time.perf_counter()
    t1 = time.perf_counter()
    recorder.complete("queued", recorder.to_us(t0), recorder.to_us(t1),
                      cat="serve", slot=3)
    (e,) = recorder.events()
    assert e["ph"] == "X" and e["dur"] >= 0 and e["args"]["slot"] == 3


def test_perfetto_export_schema(recorder, tmp_path):
    with recorder.span("op", cat="engine", track="engine", seq=0):
        recorder.instant("tick", cat="engine", track="engine")
    recorder.counter("pool", 5, track="engine")
    path = tmp_path / "trace.json"
    recorder.export(str(path))
    doc = json.loads(path.read_text())          # valid JSON
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    # metadata first: process_name + one thread_name per track
    assert evs[0] == {"name": "process_name", "ph": "M", "pid": 1,
                      "args": {"name": "repro"}}
    tracks = [e["args"]["name"] for e in evs if e["name"] == "thread_name"]
    assert "engine" in tracks
    for e in evs:
        assert {"name", "ph", "pid"} <= set(e)
        if e["ph"] == "X":
            assert {"ts", "dur"} <= set(e) and e["dur"] >= 0
        if e["ph"] == "C":
            assert "value" in e["args"]


def test_enable_starts_fresh_timeline():
    from repro import obs
    old = get_recorder()
    try:
        rec = obs.enable()
        with rec.span("x"):
            pass
        # an enabled default recorder also records GC spans
        assert [e["name"] for e in rec.events() if e["name"] != "gc"] == ["x"]
        obs.enable(False)
        rec2 = obs.enable()                     # off -> on: fresh buffer
        assert rec2.events() == []
    finally:
        set_recorder(old)


# ---------------------------------------------------------------------------
# metrics

def test_histogram_quantiles_known_values():
    m = Metrics()
    h = m.histogram("lat")
    for v in range(1, 11):                      # 1..10
        h.observe(v)
    assert h.quantile(0.0) == 1.0
    assert h.quantile(0.5) == 5.5               # numpy linear interpolation
    assert h.quantile(0.9) == pytest.approx(9.1)
    assert h.quantile(1.0) == 10.0
    assert h.quantile(0.5, values=[3.0]) == 3.0
    assert h.quantile(0.5, values=[]) == 0.0
    s = h.summary()
    assert s["count"] == 10 and s["sum"] == 55.0


def test_metrics_registry_types_and_dump(tmp_path):
    m = Metrics()
    m.counter("bytes").inc(100)
    m.gauge("pool").set(3)
    m.gauge("pool").set(1)                      # max is a high-water mark
    with pytest.raises(TypeError):
        m.histogram("bytes")
    assert m.snapshot()["pool"] == {"type": "gauge", "value": 1, "max": 3}
    path = tmp_path / "m.jsonl"
    assert m.dump_jsonl(str(path)) == 2
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert {ln["name"] for ln in lines} == {"bytes", "pool"}
    assert all(ln["kind"] == "metric" for ln in lines)


# ---------------------------------------------------------------------------
# engine stats coherence (the reset-staleness fix)

def test_engine_stats_fresh_after_reset():
    eng = reset_default_engine()
    a = Tag("a")
    for _ in range(3):
        eng.push(lambda: None, writes=(a,), name="w")
    eng.wait_all()
    eng.publish_stats()
    m = get_metrics()
    assert m.gauge("engine.ops_executed").value == 3
    assert m.histogram("engine.wave_size").count == 3
    # a fresh engine must publish fresh numbers, not accumulate onto the
    # dead instance's record
    eng2 = reset_default_engine()
    assert "engine.ops_executed" not in m.names()
    eng2.push(lambda: None, writes=(a,), name="w")
    eng2.wait_all()
    eng2.publish_stats()
    assert m.gauge("engine.ops_executed").value == 1
    assert m.histogram("engine.wave_size").count == 1


def test_engine_op_spans(recorder):
    eng = reset_default_engine()
    a, b = Tag("a"), Tag("b")
    eng.push(lambda: None, writes=(a,), name="init")
    eng.push(lambda: None, reads=(a,), writes=(b,), name="consume")
    eng.wait_all()
    spans = [e for e in recorder.events() if e["cat"] == "engine"]
    assert [s["name"] for s in spans] == ["init", "consume"]
    assert spans[1]["args"]["reads"] == ["a"]
    assert spans[1]["args"]["writes"] == ["b"]
    assert all("wave" in s["args"] for s in spans)
    reset_default_engine()


# ---------------------------------------------------------------------------
# serving lifecycle spans

def test_paged_serve_request_lifecycle(recorder):
    cfg = reduced(get_config("qwen1.5-0.5b"))
    params = get_model(cfg).init(KEY)
    rng = np.random.RandomState(3)
    prompts = [list(rng.randint(1, cfg.vocab, L)) for L in (5, 11, 19)]
    eng = PagedServeEngine(cfg, params, block_size=8, max_batch=2,
                           max_len=64, prefill_chunk=8)
    outs, stats = eng.generate(prompts, max_new_tokens=[3, 4, 6])
    assert [len(o) for o in outs] == [3, 4, 6]

    evs = recorder.events()
    doc = recorder.export()
    req_tracks = sorted(e["args"]["name"] for e in doc["traceEvents"]
                        if e.get("name") == "thread_name"
                        and e["args"]["name"].startswith("req"))
    # exactly the 3 admitted requests have tracks: the warmup throwaway
    # request (rid 0) is not observed
    assert req_tracks == ["req1", "req2", "req3"]
    for track in req_tracks:
        tids = _tids_for(recorder, track)
        mine = [e for e in evs if e["cat"] == "serve" and e["tid"] in tids]
        names = [e["name"] for e in mine]
        # complete chain: enqueued -> queued -> prefill -> first token ->
        # decode -> evicted, in timeline order
        for n in ("enqueued", "queued", "prefill_chunk", "first_token",
                  "decode", "evicted"):
            assert n in names, f"{track} missing {n}: {names}"
        by = {e["name"]: e for e in mine}
        assert by["queued"]["ts"] <= by["first_token"]["ts"]
        assert by["first_token"]["ts"] <= by["evicted"]["ts"]

    # per-run latency percentiles populated (seconds, small but positive)
    assert stats.ttft_p99 >= stats.ttft_p50 > 0
    assert stats.tpot_p99 >= stats.tpot_p50 > 0
    assert stats.queue_wait_p99 >= stats.queue_wait_p50 >= 0
    h = get_metrics().histogram("serve.ttft_s")
    assert h.count >= 3


def _tids_for(rec, track):
    doc = rec.export()
    return {e["tid"] for e in doc["traceEvents"]
            if e.get("name") == "thread_name"
            and e["args"]["name"] == track}


def test_warmup_is_not_observed(recorder):
    cfg = reduced(get_config("qwen1.5-0.5b"))
    params = get_model(cfg).init(KEY)
    eng = PagedServeEngine(cfg, params, block_size=8, max_batch=2,
                           max_len=64, prefill_chunk=8)
    before = get_metrics().histogram("serve.ttft_s").count
    eng.warmup()
    assert get_metrics().histogram("serve.ttft_s").count == before
    assert eng._observe is True                 # restored after warmup


# ---------------------------------------------------------------------------
# engine step phases and counts, compile and GC spans, the profiler's clock

# the top-level phases of PagedServeEngine.step, in order (a prefill
# chunk's build and the chunk repeat as a pair), and the children of the
# two that touch the device
PHASES = ["admit", "prefill_build", "prefill_chunk", "first_token",
          "batch_build", "decode_step", "retire"]
RANK = dict(admit=0, prefill_build=1, prefill_chunk=1,
            first_token=2, batch_build=3, decode_step=4, retire=5)
CHILDREN = {"prefill_chunk": ["prefill_dispatch", "prefill_wait"],
            "decode_step": ["decode_dispatch", "decode_sync"]}


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced(get_config("qwen1.5-0.5b"))
    return cfg, get_model(cfg).init(KEY)


def _engine(tiny, max_batch=2):
    cfg, params = tiny
    return PagedServeEngine(cfg, params, block_size=8, max_batch=max_batch,
                            max_len=64, prefill_chunk=8)


def _prompts(cfg, lengths=(5, 11, 19, 9)):
    rng = np.random.RandomState(3)
    return [list(rng.randint(1, cfg.vocab, n)) for n in lengths]


def _inside(outer, events):
    end = outer["ts"] + outer["dur"]
    return sorted((e for e in events if e is not outer
                   and outer["ts"] <= e["ts"] and e["ts"] + e["dur"] <= end),
                  key=lambda e: e["ts"])


def test_engine_step_holds_its_phases_in_order(recorder, tiny):
    eng = _engine(tiny)
    eng.warmup()
    recorder.clear()
    prompts = _prompts(tiny[0])
    eng.generate(prompts, max_new_tokens=[3, 4, 6, 2], warmup=False)
    spans = [e for e in recorder.events() if e["ph"] == "X"]
    steps = [e for e in spans if e["name"] == "engine_step"]
    assert steps and len(_tids_for(recorder, "serve")) == 1
    seen = set()
    for st in steps:
        top = [e for e in _inside(st, spans) if e["name"] in PHASES]
        names = [e["name"] for e in top]
        assert names[0] == "admit"
        order = [RANK[n] for n in names]
        assert order == sorted(order), names
        # each chunk's host arrays are built just before it runs
        assert all(names[i - 1] == "prefill_build"
                   for i, n in enumerate(names) if n == "prefill_chunk")
        for e in top:
            if e["name"] in CHILDREN:
                kids = [k["name"] for k in _inside(e, spans)]
                assert kids == CHILDREN[e["name"]]
        seen.update(names)
    assert seen == set(PHASES)
    # the two spans the benchmark's readers match keep their tracks, args
    serve = _tids_for(recorder, "serve")
    chunks = [e for e in spans if e["name"] == "prefill_chunk"]
    req_tids = {t for r in eng.results for t in _tids_for(recorder, f"req{r}")}
    assert {e["tid"] for e in chunks} == req_tids
    for e in chunks:
        assert set(e["args"]) == {"slot", "start", "tokens"}
    decodes = [e for e in spans if e["name"] == "decode_step"]
    assert decodes and all(e["tid"] in serve and e["args"]["lanes"] >= 1
                           for e in decodes)


def test_engine_step_counts_each_steps_work(recorder, tiny):
    """Two lanes, chunks of 8, one chunk a step: prompts of 5, 11, 19 and
    9 tokens for 3, 4, 6 and 2 new ones.  A lane decodes from the step
    its prefill completes (its first token comes from the prefill), so
    the counts follow from the schedule."""
    eng = _engine(tiny)
    eng.warmup()
    recorder.clear()
    eng.generate(_prompts(tiny[0]), max_new_tokens=[3, 4, 6, 2],
                 warmup=False)
    counts = [(e["args"]["lanes"], e["args"]["prefill_tokens"])
              for e in recorder.events() if e["name"] == "engine_step"]
    assert counts == [
        (1, 5),     # request 0 prefills whole and decodes
        (1, 8),     # request 1's first chunk; request 0 ends
        (0, 8),     # request 2 takes lane 0: its first two chunks
        (0, 8),
        (1, 3),     # request 2's last chunk, and it decodes
        (2, 3),     # request 1's last chunk: both lanes decode
        (2, 0),
        (2, 0),     # request 1 ends
        (1, 8),     # request 3 takes lane 1
        (1, 1)]     # all four end
    assert sum(p for _, p in counts) == 5 + 11 + 19 + 9
    assert sum(n for n, _ in counts) == (3 - 1) + (4 - 1) + (6 - 1) + (2 - 1)
    assert all(set(e["args"]) == {"lanes", "prefill_tokens"}
               for e in recorder.events() if e["name"] == "engine_step")


def test_disabled_recorder_builds_nothing_and_adds_no_sync(tiny,
                                                           monkeypatch):
    """Off, a step constructs no TraceAnnotation and leaves gc.callbacks
    as it found them; on, it makes exactly the device syncs it makes
    off (the counts come from host integers)."""
    import jax.profiler
    from jax._src import array
    from repro import obs
    from repro.obs import trace
    from repro.serve import engine as engine_mod
    made, syncs = [], []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    value = array.ArrayImpl._value
    wait = array.ArrayImpl.block_until_ready

    def counted_wait(self):
        syncs.append("wait")
        return wait(self)

    class CountingNumpy:
        """The engine's ``np``, counting conversions of device arrays (on
        the CPU they read the buffer directly, past ``_value``)."""

        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, a, *args, **kw):
            if isinstance(a, jax.Array):
                syncs.append("asarray")
            return np.asarray(a, *args, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(array.ArrayImpl, "_value", property(
        lambda self: (syncs.append("fetch"), value.fget(self))[1]))
    monkeypatch.setattr(array.ArrayImpl, "block_until_ready", counted_wait)
    monkeypatch.setattr(engine_mod, "np", CountingNumpy())
    cfg = tiny[0]

    def run():
        made.clear()
        syncs.clear()
        eng = _engine(tiny)
        eng.generate(_prompts(cfg), max_new_tokens=[3, 4, 6, 2],
                     warmup=False)
        return list(syncs)

    old = get_recorder()
    callbacks = list(gc.callbacks)
    try:
        set_recorder(TraceRecorder(enabled=False))
        run()                                   # compiles every shape
        off = run()
        assert made == [] and gc.callbacks == callbacks
        obs.enable(True)
        assert trace._on_gc in gc.callbacks
        on = run()
        assert "engine_step" in made and "decode_sync" in made
        assert on == off
        assert off.count("wait") > 0 and off.count("asarray") > 0
        obs.enable(False)
        assert gc.callbacks == callbacks
    finally:
        set_recorder(old)
    assert gc.callbacks == callbacks


def test_compiles_and_collections_become_spans():
    import jax.numpy as jnp
    from repro import obs
    old = get_recorder()
    try:
        rec = obs.enable()

        def triple_plus_one(x):
            return x * 3 + 1

        jax.jit(triple_plus_one)(jnp.ones((7, 13)))    # a fresh program
        gc.collect()
        obs.enable(False)
    finally:
        set_recorder(old)
    evs = rec.events()
    comp = [e for e in evs if e["name"] == "compile"
            and "triple_plus_one" in e["args"]["fun_name"]]
    # one XLA compile, besides its tracing and lowering stages
    stages = [e["args"]["stage"] for e in comp]
    assert stages.count("backend_compile_duration") == 1
    assert "jaxpr_trace_duration" in stages
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in comp)
    assert _tids_for(rec, "jit") == {e["tid"] for e in comp}
    gcs = [e for e in evs if e["name"] == "gc"]
    assert any(e["args"]["generation"] == 2 for e in gcs)
    assert _tids_for(rec, "gc") == {e["tid"] for e in gcs}
    # off again: a compile records nothing
    n = len(rec.events())
    jax.jit(lambda x: x - 5)(jnp.ones((3, 17)))
    assert len(rec.events()) == n


def test_profiler_trace_holds_the_span_names(tiny, tmp_path):
    """Each span is also a TraceAnnotation: a CPU profiler trace holds the
    step's phases on its host plane, on the device ops' clock."""
    from jax.profiler import ProfileData
    from repro import obs
    eng = _engine(tiny)
    eng.warmup()
    old = get_recorder()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        obs.enable()
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            eng.generate(_prompts(tiny[0])[:2], max_new_tokens=[3, 2],
                         warmup=False)
        finally:
            jax.profiler.stop_trace()
            obs.enable(False)
    finally:
        set_recorder(old)
    (path,) = tmp_path.glob("**/*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"engine_step", *PHASES, *CHILDREN["prefill_chunk"],
            *CHILDREN["decode_step"]} <= names
