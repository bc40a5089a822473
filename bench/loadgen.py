"""The one traffic generator: it reads a traffic file's parameters and
drives the engine through ``submit`` -> ``step`` for one measured window.

* ``open``: requests fall due on a schedule whatever the server does:
  ``rate_per_s * seconds`` due times drawn uniformly over the window from
  the seed, and sorted. That is a Poisson process held to its expected
  count, so every seed offers the same work, with the bursts and lulls of
  independent users in a seeded arrangement. Each request is
  stamped with its due time (``CNNRequest.t_submit`` on the engine's
  clock), and its latency runs from that due time to the moment its logits
  are in host memory, so a stall is charged to every request it delays.
* ``closed``: ``clients`` clients each send their next image as soon as
  the last one's logits are in host memory.

Images cycle round robin through a pool of distinct seeded images. The
host spans ``client.wait``, ``client.submit``, ``engine.step`` and
``client.collect`` mark what the loop is doing, and ``bench.window`` the
measured window; ``HostSpans`` records them on the host's monotonic
clock. ``at=(seconds, fn)`` calls ``fn`` once, from the loop between two
ticks, when the window has run that long.
"""
from __future__ import annotations

import dataclasses
import time
from contextlib import contextmanager, nullcontext
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

# How long after the window's close the loop still waits for answers.
GRACE_S = 60.0
# The engine's default clock: due times and answers are read on it.
CLOCK = time.monotonic


def open_schedule(rate_per_s: float, seconds: float, seed: int
                  ) -> np.ndarray:
    """Due times (seconds from the window's start) of an open loop: a
    Poisson process at the rate, conditioned on its expected count, i.e.
    that many uniform times over the window, sorted."""
    n = max(1, int(round(rate_per_s * seconds)))
    rng = np.random.default_rng([seed, 2])
    return np.sort(rng.uniform(0.0, seconds, n))


class HostSpans:
    """A ``span`` that records ``(name, start_ns, end_ns)`` on
    ``time.monotonic_ns``, the clock ``CLOCK`` reads."""

    def __init__(self) -> None:
        self.spans: List[Tuple[str, int, int]] = []

    @contextmanager
    def __call__(self, name: str):
        t0 = time.monotonic_ns()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.monotonic_ns()))


class _At:
    """Calls ``fn`` once, the first time it is polled ``at_s`` or more
    after ``t0``."""

    def __init__(self, at: Optional[Tuple[float, Callable[[], None]]],
                 t0: float) -> None:
        self.due = None if at is None else t0 + at[0]
        self.fn = None if at is None else at[1]

    def poll(self, now: float) -> None:
        if self.due is not None and now >= self.due:
            self.due = None
            self.fn()


@dataclasses.dataclass
class Window:
    """What one measured window did, on the engine's clock."""
    kind: str
    seconds: float
    t0: float = 0.0
    t_end: float = 0.0
    attempted: int = 0
    # Per request id: pool image, due or submit time, time its logits
    # reached the host (NaN until then), and the logits.
    image: Dict[int, int] = dataclasses.field(default_factory=dict)
    t_sent: Dict[int, float] = dataclasses.field(default_factory=dict)
    t_done: Dict[int, float] = dataclasses.field(default_factory=dict)
    logits: Dict[int, np.ndarray] = dataclasses.field(default_factory=dict)
    late_s: List[float] = dataclasses.field(default_factory=list)
    queue_s: List[float] = dataclasses.field(default_factory=list)
    dispatched: Dict[int, int] = dataclasses.field(default_factory=dict)
    served: int = 0
    completed_in_window: int = 0

    @property
    def window_s(self) -> float:
        return self.t_end - self.t0

    @property
    def unanswered(self) -> int:
        return self.attempted - len(self.t_done)

    def latencies_s(self) -> np.ndarray:
        """Every attempted request's latency; one never answered is inf."""
        return np.array([self.t_done.get(r, np.inf) - t
                         for r, t in self.t_sent.items()])


class _Client:
    def __init__(self, engine, pool: np.ndarray, win: Window,
                 span: Callable) -> None:
        from repro.serving.cnn_engine import CNNRequest
        self.engine, self.pool, self.win, self.span = engine, pool, win, span
        self._req = CNNRequest
        self._dispatch0 = dict(engine.dispatches)
        self._served0 = engine.served_total
        engine.request_log.clear()

    def send(self, rid: int, t_submit: Optional[float]) -> None:
        img = rid % len(self.pool)
        self.win.image[rid] = img
        self.engine.submit(self._req(rid=rid, image=self.pool[img],
                                     t_submit=t_submit))
        self.win.attempted += 1

    def step(self) -> List[int]:
        """One engine tick; returns the ids whose logits reached the host."""
        eng = self.engine
        with self.span("engine.step"):
            eng.step()
        with self.span("client.collect"):
            now = CLOCK()
            rids = list(eng.done)
            for rid in rids:
                self.win.t_done[rid] = now
                self.win.logits[rid] = eng.done.pop(rid)
            log = eng.request_log
            while log:
                self.win.queue_s.append(log.popleft().queue_s)
        return rids

    def close(self) -> None:
        eng = self.engine
        self.win.dispatched = {b: n - self._dispatch0.get(b, 0)
                               for b, n in eng.dispatches.items()}
        self.win.served = eng.served_total - self._served0


def run_open(engine, pool: np.ndarray, traffic: Dict, seconds: float,
             seed: int, span: Callable = lambda name: nullcontext(),
             at: Optional[Tuple[float, Callable[[], None]]] = None
             ) -> Window:
    win = Window("open", seconds)
    clock = CLOCK
    due = open_schedule(float(traffic["rate_per_s"]), seconds, seed)
    cl = _Client(engine, pool, win, span)
    n = len(due)
    win.t0 = t0 = clock()
    due_abs = t0 + due
    fire = _At(at, t0)
    i = 0
    with span("bench.window"):
        while True:
            now = clock()
            fire.poll(now)
            if i < n and due_abs[i] <= now:
                with span("client.submit"):
                    while i < n and due_abs[i] <= now:
                        win.t_sent[i] = float(due_abs[i])
                        cl.send(i, float(due_abs[i]))
                        win.late_s.append(clock() - due_abs[i])
                        i += 1
            if engine.queue:
                cl.step()
            elif i < n:
                with span("client.wait"):
                    time.sleep(max(0.0, due_abs[i] - clock()))
            else:
                break
            if now > t0 + seconds + GRACE_S:
                break
    win.t_end = max(t0 + seconds, max(win.t_done.values(), default=t0))
    win.completed_in_window = len(win.t_done)
    cl.close()
    return win


def run_closed(engine, pool: np.ndarray, traffic: Dict, seconds: float,
               seed: int, span: Callable = lambda name: nullcontext(),
               at: Optional[Tuple[float, Callable[[], None]]] = None
               ) -> Window:
    """The window closes at the first tick boundary after ``seconds``;
    requests still queued then are answered afterwards, and count as
    attempted but not as completed in the window."""
    win = Window("closed", seconds)
    clock = CLOCK
    cl = _Client(engine, pool, win, span)
    # The seed picks where in the pool the clients start.
    first = int(np.random.default_rng([seed, 3]).integers(len(pool)))
    next_rid = first

    def send_one() -> None:
        nonlocal next_rid
        win.t_sent[next_rid] = clock()
        cl.send(next_rid, None)
        next_rid += 1

    win.t0 = clock()
    fire = _At(at, win.t0)
    with span("bench.window"):
        with span("client.submit"):
            for _ in range(int(traffic["clients"])):
                send_one()
        while True:
            done = cl.step()
            now = clock()
            if now >= win.t0 + seconds:
                break
            fire.poll(now)
            with span("client.submit"):
                for _ in done:
                    send_one()
    win.t_end = now
    win.completed_in_window = len(win.t_done)
    while engine.queue and clock() < win.t_end + GRACE_S:
        cl.step()
    cl.close()
    return win


RUNNERS = {"open": run_open, "closed": run_closed}
