"""The traffic generator: determinism per seed, due-time stamping, and the
closed loop's window."""
import collections

import numpy as np
import pytest

from bench import loadgen


class StubEngine:
    """Answers every queued request at once, one tick per ``step``, after
    ``tick_s`` seconds; records what it was given."""

    def __init__(self, tick_s: float = 0.0) -> None:
        self.tick_s = tick_s
        self.queue = []
        self.done = {}
        self.request_log = collections.deque(maxlen=4)
        self.dispatches = {1: 0, 2: 0, 4: 0}
        self.served_total = 0
        self.submitted = []

    def submit(self, req):
        if req.t_submit is None:
            req.t_submit = loadgen.CLOCK()
        self.queue.append(req)
        self.submitted.append(req)
        return "queued"

    def step(self):
        import time
        batch, self.queue = self.queue[:4], self.queue[4:]
        if not batch:
            return 0
        time.sleep(self.tick_s)
        now = loadgen.CLOCK()
        bucket = next(b for b in (1, 2, 4) if b >= len(batch))
        self.dispatches[bucket] += 1
        for r in batch:
            self.done[r.rid] = np.full(3, float(r.rid))
            self.request_log.append(type("T", (), {
                "queue_s": now - r.t_submit})())
        self.served_total += len(batch)
        return len(batch)


def test_open_schedule_is_fixed_work_in_seeded_order():
    a = loadgen.open_schedule(500.0, 2.0, 2**33 + 1)
    b = loadgen.open_schedule(500.0, 2.0, 2**33 + 1)
    c = loadgen.open_schedule(500.0, 2.0, 7)
    np.testing.assert_array_equal(a, b)
    assert len(a) == len(c) == 1000
    assert not np.array_equal(a, c)
    for due in (a, c):
        assert np.all(np.diff(due) >= 0)
        assert 0.0 <= due[0] and due[-1] < 2.0
    # Poisson gaps: exponential, mean 1/rate, median ln 2 / rate, and a
    # coefficient of variation near 1.
    gaps = np.diff(np.concatenate([[0.0], a]))
    assert np.mean(gaps) == pytest.approx(1 / 500.0, rel=0.05)
    assert np.median(gaps) == pytest.approx(np.log(2) / 500.0, rel=0.15)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.1)


def test_every_stratum_offers_the_same_load():
    """Every quarter second offers the same load in expectation, with the
    spread of independent arrivals: counts vary as a Poisson count does,
    and every seed offers the same total."""
    due = loadgen.open_schedule(600.0, 10.0, 2**31 + 9)
    assert len(due) == 6000
    assert len(loadgen.open_schedule(600.0, 10.0, 3)) == 6000
    counts = np.diff(np.searchsorted(due, np.arange(41) * 0.25))
    assert counts.sum() == 6000
    assert np.mean(counts) == pytest.approx(150.0)
    # A Poisson count's variance equals its mean (150; sd about 12).
    assert 75.0 < np.var(counts) < 260.0
    assert counts.max() - counts.min() > 20


def test_open_loop_stamps_due_times_and_times_from_them():
    eng = StubEngine(tick_s=0.02)
    pool = np.zeros((5, 2, 2, 3), np.float32)
    traffic = {"rate_per_s": 200.0}
    win = loadgen.run_open(eng, pool, traffic, 0.3, seed=3)
    due = loadgen.open_schedule(200.0, 0.3, 3)
    assert win.attempted == len(due) == 60
    assert win.unanswered == 0
    stamped = np.array([r.t_submit for r in eng.submitted]) - win.t0
    np.testing.assert_allclose(stamped, due, atol=1e-9)
    # A request that waited behind a 20 ms tick is charged that wait.
    lat = win.latencies_s()
    assert lat.min() >= 0.0 and lat.max() >= 0.02
    assert [win.image[r] for r in range(7)] == [0, 1, 2, 3, 4, 0, 1]
    for rid, out in win.logits.items():
        assert out[0] == rid
    assert len(win.queue_s) == 60
    assert win.served == 60
    assert sum(b * n for b, n in win.dispatched.items()) >= 60


def test_open_loop_charges_a_stall_to_everyone_behind_it():
    eng = StubEngine(tick_s=0.1)
    pool = np.zeros((2, 2, 2, 3), np.float32)
    traffic = {"rate_per_s": 100.0}
    win = loadgen.run_open(eng, pool, traffic, 0.2, seed=1)
    # 20 requests over 0.2 s; ticks of 4 take 0.1 s, so the backlog grows
    # and the last answers come well after the window's close.
    assert win.window_s > 0.3
    assert np.percentile(win.latencies_s(), 95) > 0.2


def test_closed_loop_keeps_its_clients_busy():
    eng = StubEngine(tick_s=0.005)
    pool = np.zeros((64, 2, 2, 3), np.float32)
    win = loadgen.run_closed(eng, pool, {"clients": 4}, 0.1, seed=9)
    assert win.unanswered == 0
    assert win.completed_in_window >= 4 * 10
    # Every tick after the first carries all four clients.
    assert eng.dispatches[4] == win.attempted // 4
    first = min(win.image.items())[1]
    again = loadgen.run_closed(StubEngine(), pool, {"clients": 1}, 0.01,
                               seed=9)
    assert min(again.image.items())[1] == first


@pytest.mark.parametrize("kind", ["open", "closed"])
def test_host_spans_and_a_call_at_a_time_into_the_window(kind):
    eng = StubEngine(tick_s=0.002)
    pool = np.zeros((4, 2, 2, 3), np.float32)
    spans = loadgen.HostSpans()
    fired = []
    at = (0.05, lambda: fired.append(loadgen.CLOCK()))
    if kind == "open":
        win = loadgen.run_open(eng, pool, {"rate_per_s": 400.0}, 0.1, 5,
                               span=spans, at=at)
    else:
        win = loadgen.run_closed(eng, pool, {"clients": 2}, 0.1, 5,
                                 span=spans, at=at)
    assert len(fired) == 1
    assert win.t0 + 0.05 <= fired[0] < win.t0 + 0.1
    names = [n for n, _, _ in spans.spans]
    assert names.count("bench.window") == 1
    assert names.count("engine.step") >= 10
    window = next((s, e) for n, s, e in spans.spans if n == "bench.window")
    assert window[0] <= win.t0 * 1e9 + 1e6
    for n, s, e in spans.spans:
        assert window[0] <= s <= e <= window[1]
