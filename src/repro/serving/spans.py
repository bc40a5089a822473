"""A bounded in-memory record of named host spans.

``HostSpans`` is the one span recorder of the serving stack: a serving
loop wraps its own phases in it (``with spans("engine.step"): ...``), and
``CNNServingEngine(spans=...)`` records the phases of each tick inside
those. Each record is ``(name, start_ns, end_ns)`` on
``time.monotonic_ns``, the clock that the engine's default clock,
``time.monotonic``, reads. Records are appended when a span closes, so a
span that encloses others is recorded after them.

The record keeps the newest ``maxlen`` spans, as ``trace_window`` bounds
the engine's ``request_log``, so a long-running server can keep it on;
``dropped`` counts the spans pushed out.
"""
from __future__ import annotations

import collections
import time
from typing import Deque, Tuple

Span = Tuple[str, int, int]


class _Open:
    """One span while it is open."""
    __slots__ = ("_rec", "_name", "_t0")

    def __init__(self, rec: "HostSpans", name: str) -> None:
        self._rec = rec
        self._name = name

    def __enter__(self) -> None:
        self._t0 = time.monotonic_ns()

    def __exit__(self, *exc) -> None:
        self._rec._add((self._name, self._t0, time.monotonic_ns()))


class HostSpans:
    """``with spans(name): ...`` records ``(name, start_ns, end_ns)``."""

    def __init__(self, maxlen: int = 1 << 16) -> None:
        if maxlen < 1:
            raise ValueError(f"maxlen must be >= 1, got {maxlen}")
        self.spans: Deque[Span] = collections.deque(maxlen=maxlen)
        self.recorded = 0

    def __call__(self, name: str) -> _Open:
        return _Open(self, name)

    def _add(self, span: Span) -> None:
        self.spans.append(span)
        self.recorded += 1

    @property
    def dropped(self) -> int:
        """Spans pushed out of the record by newer ones."""
        return self.recorded - len(self.spans)

    def clear(self) -> None:
        self.spans.clear()
        self.recorded = 0
