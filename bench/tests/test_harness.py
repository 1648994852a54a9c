"""The harness's own logic on the CPU, with no model: open-loop timing on a
fake clock, the backlog window's opening, the end-to-end metrics, the
traffic generator and the result line."""
from __future__ import annotations

import json
from types import SimpleNamespace

import numpy as np
import pytest

from bench import common, serve_driver, traffic


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def now(self):
        return self.t

    def sleep(self, dt):
        self.t += max(dt, 0.0)


class FakeEngine:
    """The slice of ``PagedServeEngine`` the drivers use: each step takes
    ``step_s`` on the fake clock, admits into free lanes, prefills a whole
    prompt (emitting its first token) or decodes one token per lane."""

    def __init__(self, clock, step_s, max_batch):
        self.clock, self.step_s = clock, step_s
        self.slots = [None] * max_batch
        self.pending, self.results = [], {}
        self._rid = 0

    @property
    def busy(self):
        return bool(self.pending) or any(s is not None for s in self.slots)

    def add_request(self, prompt, max_new):
        r = SimpleNamespace(rid=self._rid, seq=list(prompt), prefilled=0,
                            out=[], max_new=max_new)
        self._rid += 1
        self.pending.append(r)
        return SimpleNamespace(accepted=True, rid=r.rid)

    def step(self):
        self.clock.t += self.step_s
        for i, s in enumerate(self.slots):
            if s is None and self.pending:
                self.slots[i] = self.pending.pop(0)
        for i, r in enumerate(self.slots):
            if r is None:
                continue
            if r.prefilled < len(r.seq):
                r.prefilled = len(r.seq)
            r.out.append(7)
            if len(r.out) >= r.max_new:
                self.results[r.rid] = SimpleNamespace(
                    status=SimpleNamespace(name="OK"), tokens=list(r.out))
                self.slots[i] = None


def _reqs(dues, new=3):
    return [traffic.Request(i, d, [1, 2, 3], new) for i, d in enumerate(dues)]


def test_open_loop_times_each_request_from_its_due_time():
    clock = FakeClock()
    eng = FakeEngine(clock, step_s=0.1, max_batch=4)
    mix = {"warm_s": 0.0}
    window = common.Window(clock)
    tr, in_window, late = serve_driver.run_open_loop(
        eng, _reqs([0.0, 0.12, 0.30]), mix, 1.0, clock, window)
    start = window.t0
    # request 1 is due at 0.12, waits for the step that ends at 0.2, and
    # gets its first token at the end of the next step (0.3): 0.18 from
    # its due time, of which 0.08 is the wait for the running step
    assert tr.recs[1].t_first - tr.recs[1].due == pytest.approx(0.18)
    assert tr.recs[1].due - start == pytest.approx(0.12)
    assert late[1] == pytest.approx(0.08)
    assert all(0.0 <= x <= 0.1 + 1e-9 for x in late)
    metrics, attempted, failed = serve_driver.serve_metrics(
        tr, window, in_window, "open_loop")
    assert attempted == 3 and failed == 0
    # three tokens each, one step apart: two 100 ms gaps a request
    assert len(tr.gaps) == 6
    assert metrics["tpot_p95_ms"] == pytest.approx(100.0)
    # the run ends with the window
    assert window.t1 - start == pytest.approx(1.0, abs=0.1)


def test_open_loop_tail_is_over_every_token_of_the_window():
    """A slow step delays one token of each running request: the tail
    sees it in every request it touched, however few the requests."""
    clock = FakeClock()
    eng = FakeEngine(clock, step_s=0.1, max_batch=4)
    slow = {"n": 0}
    step = eng.step

    def step_with_a_stall():
        slow["n"] += 1
        if slow["n"] % 10 == 0:
            clock.t += 0.4          # every tenth step takes 0.5 s
        step()

    eng.step = step_with_a_stall
    window = common.Window(clock)
    tr, in_window, _ = serve_driver.run_open_loop(
        eng, _reqs([0.0, 0.0], new=200), {"warm_s": 0.5}, 10.0, clock,
        window)
    metrics, attempted, failed = serve_driver.serve_metrics(
        tr, window, in_window, "open_loop")
    gaps = [1e3 * g for t, g in tr.gaps if window.t0 < t <= window.t1]
    assert len(gaps) > 100 and attempted == 0 and failed == 0
    assert metrics["tpot_p95_ms"] == pytest.approx(np.percentile(gaps, 95))
    assert metrics["tpot_p95_ms"] == pytest.approx(500.0)


def test_open_loop_window_counts_only_requests_due_inside_it():
    clock = FakeClock()
    eng = FakeEngine(clock, step_s=0.05, max_batch=8)
    mix = {"warm_s": 0.5}
    window = common.Window(clock)
    dues = [0.1, 0.4, 0.6, 0.9, 1.2, 1.6]
    tr, in_window, _ = serve_driver.run_open_loop(
        eng, _reqs(dues), mix, 1.0, clock, window)
    assert in_window == [2, 3, 4]          # due in [0.5, 1.5)
    assert 5 not in tr.recs                # due after the window closed
    assert all(tr.recs[i].done for i in in_window)


def test_backlog_window_opens_only_after_the_lanes_fill():
    clock = FakeClock()
    eng = FakeEngine(clock, step_s=0.01, max_batch=4)
    mix = {"queue_depth": 2}
    window = common.Window(clock)
    reqs = _reqs([0.0] * 40, new=5)
    tr, _ = serve_driver.run_backlog(eng, reqs, mix, 0.2, clock, window,
                                     max_batch=4)
    first_tokens = [tr.recs[i].t_first for i in range(4)]
    assert window.t0 >= max(first_tokens)
    assert window.t1 - window.t0 == pytest.approx(0.2, abs=0.011)


def test_traffic_every_seed_offers_the_same_sizes_in_another_order():
    mix = {"kind": "open_loop", "rate_per_s": 10.0, "warm_s": 5.0,
           "prompt": {"median": 768, "sigma": 0.9, "min": 32, "max": 3584},
           "output": {"median": 192, "sigma": 0.8, "min": 16, "max": 512}}
    a = traffic.make_requests(mix, 1000, 2**33 + 5, seconds=10.0)
    b = traffic.make_requests(mix, 1000, 11, seconds=10.0)
    assert len(a) == len(b) == 150
    # the window [5, 15) holds the same count and sizes for every seed
    win = [[r for r in x if 5.0 <= r.due < 15.0] for x in (a, b)]
    assert len(win[0]) == len(win[1]) == 100
    assert sorted(len(r.prompt) for r in win[0]) == sorted(
        len(r.prompt) for r in win[1])
    assert sorted(r.max_new for r in win[0]) == sorted(r.max_new
                                                       for r in win[1])
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert all(0.0 < r.due < 15.0 for r in a)
    again = traffic.make_requests(mix, 1000, 2**33 + 5, seconds=10.0)
    assert [r.prompt for r in a] == [r.prompt for r in again]
    lens = np.array([len(r.prompt) for r in a])
    assert lens.min() >= 32 and lens.max() <= 3584
    assert np.median(lens) == pytest.approx(768, rel=0.05)
    gaps = np.diff([r.due for r in win[0]])
    assert np.mean(gaps) == pytest.approx(0.1, rel=0.05)
    assert np.std(gaps) == pytest.approx(0.1, rel=0.35)   # Poisson: cv 1
    # a backlog: every block of queue_depth requests holds the same sizes
    back = dict(mix, kind="backlog", requests=100, queue_depth=16)
    a, b = (traffic.make_requests(back, 1000, s, seconds=10.0)
            for s in (2**33 + 5, 11))
    assert len(a) == 100 and all(r.due == 0.0 for r in a)
    for lo in range(0, 96, 16):
        assert sorted(len(r.prompt) for r in a[lo:lo + 16]) == sorted(
            len(r.prompt) for r in b[lo:lo + 16])
        assert sorted(r.max_new for r in a[lo:lo + 16]) == sorted(
            r.max_new for r in b[lo:lo + 16])


def test_result_line_keys_and_checks_last():
    line = common.result_line(
        correct=True, attempted=3, failed=0,
        metrics={"setup_s": {"value": 1.5, "unit": "s"}},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 10},
        checks={"logit_gap": {"value": 0.01, "limit": 0.1}},
        breakdown={"device_ops": [], "idle_gaps": []})
    out = json.loads(line)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "checks"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(common.BenchError):
        common.peaks_for("TPU v99")
    assert common.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_every_cell_finds_its_files_and_metrics_by_name():
    bench = common.load_json(common.ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = common.load_cell(w["name"])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert (common.BENCH / "metrics" / f"{m['name']}.py").exists()
