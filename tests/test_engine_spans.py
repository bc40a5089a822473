"""The serving stack's span recorder and the engine's tick spans.

``serving.spans.HostSpans`` records ``(name, start_ns, end_ns)`` on the
monotonic clock, keeping the newest ``maxlen``. ``CNNServingEngine(
spans=...)`` records ``engine.stage``, ``engine.launch``, ``engine.block``
and ``engine.unpack`` for every tick, nested in the caller's span around
``step()``; ``RequestTrace.tick`` joins a request to its tick. Without a
recorder the engine records nothing and computes the same logits.
"""
import time

import numpy as np
import pytest

import jax

from repro.cnn.executor import init_params
from repro.cnn.models import vgg16
from repro.distributed.fault import FaultPlan, TickFault
from repro.serving.cnn_engine import (OUTCOME_COMPLETED, OUTCOME_FAILED,
                                      OUTCOME_REJECTED, CNNRequest,
                                      CNNServingEngine)
from repro.serving.spans import HostSpans

RNG = np.random.default_rng(41)
# The spans of one tick, in the order a synchronous tick records them.
TICK_SPANS = ("engine.stage", "engine.launch", "engine.block",
              "engine.unpack")


@pytest.fixture(scope="module")
def tiny():
    g = vgg16(res=8, scale=0.05)
    params = init_params(g, jax.random.PRNGKey(0))
    return g, params


def images(n):
    return np.asarray(RNG.standard_normal((n, 8, 8, 3)), np.float32)


def serve(eng, rec, imgs, per_tick):
    """Submit ``per_tick`` images before each tick, wrapping each
    ``step()`` (and the final drain) in the caller's ``engine.step``."""
    for at in range(0, len(imgs), per_tick):
        for rid in range(at, min(at + per_tick, len(imgs))):
            eng.submit(CNNRequest(rid=rid, image=imgs[rid]))
        with rec("engine.step"):
            eng.step()
    with rec("engine.step"):
        eng.drain()


def by_name(rec, name):
    return [(s, e) for n, s, e in rec.spans if n == name]


def test_recorder_records_names_and_times_on_the_monotonic_clock():
    rec = HostSpans()
    before = time.monotonic_ns()
    with rec("outer"):
        with rec("inner"):
            time.sleep(0.001)
    after = time.monotonic_ns()
    (n1, s1, e1), (n2, s2, e2) = rec.spans
    # A span is recorded when it closes: the inner one first.
    assert (n1, n2) == ("inner", "outer")
    assert before <= s2 <= s1 <= e1 <= e2 <= after
    assert e1 - s1 >= 1_000_000
    # A span that raises is still recorded.
    with pytest.raises(KeyError):
        with rec("raises"):
            raise KeyError
    assert rec.spans[-1][0] == "raises"


def test_recorder_bound_keeps_the_newest_spans():
    rec = HostSpans(maxlen=3)
    for i in range(5):
        with rec(f"s{i}"):
            pass
    assert [n for n, _, _ in rec.spans] == ["s2", "s3", "s4"]
    assert rec.recorded == 5 and rec.dropped == 2
    rec.clear()
    assert len(rec.spans) == 0 and rec.dropped == 0
    with pytest.raises(ValueError, match="maxlen"):
        HostSpans(maxlen=0)


@pytest.mark.parametrize("depth", [1, 2])
def test_every_tick_records_its_four_spans_in_order_inside_a_step(tiny,
                                                                  depth):
    g, params = tiny
    rec = HostSpans()
    eng = CNNServingEngine(g, params, None, batch_size=2,
                           pipeline_depth=depth, spans=rec)
    imgs = images(9)
    serve(eng, rec, imgs, per_tick=2)
    assert len(eng.done) == 9
    ticks = 5
    per = {name: by_name(rec, name) for name in TICK_SPANS}
    assert all(len(v) == ticks for v in per.values())
    steps = by_name(rec, "engine.step")
    for k in range(ticks):
        stage, launch, block, unpack = (per[n][k] for n in TICK_SPANS)
        assert stage[1] <= launch[0] and launch[1] <= block[0]
        assert block[1] <= unpack[0]
        for s, e in (stage, launch, block, unpack):
            assert any(a <= s and e <= b for a, b in steps)
    if depth == 1:
        # Synchronous: a tick's four spans lie in the step that made it.
        for k in range(ticks):
            a, b = steps[k]
            assert all(a <= per[n][k][0] and per[n][k][1] <= b
                       for n in TICK_SPANS)
    else:
        # Pipelined: tick k's block and unpack run in a later step.
        a, b = steps[0]
        assert not any(a <= s and e <= b for s, e in per["engine.block"])


def test_request_trace_joins_each_request_to_its_tick(tiny):
    g, params = tiny
    rec = HostSpans()
    eng = CNNServingEngine(g, params, None, batch_size=2, max_queue=4,
                           spans=rec,
                           fault_plan=FaultPlan({1: TickFault(failures=9)}),
                           max_retries=0)
    serve(eng, rec, images(6), per_tick=2)
    for i in range(5):
        eng.submit(CNNRequest(rid=100 + i, image=images(1)[0]))
    log = {t.rid: t for t in eng.request_log}
    for rid in range(6):
        assert log[rid].tick == rid // 2
    assert log[2].outcome == log[3].outcome == OUTCOME_FAILED
    assert log[0].outcome == log[5].outcome == OUTCOME_COMPLETED
    assert log[104].outcome == OUTCOME_REJECTED and log[104].tick is None
    # Every tick staged and launched, in tick order; the failed one
    # (completion-surfaced, no retries) blocked but never unpacked.
    assert len(by_name(rec, "engine.launch")) == 3
    assert len(by_name(rec, "engine.block")) == 3
    assert len(by_name(rec, "engine.unpack")) == 2


def test_without_a_recorder_nothing_is_recorded_and_logits_match(tiny):
    g, params = tiny
    imgs = images(7)
    rec = HostSpans()
    on = CNNServingEngine(g, params, None, batch_size=4, spans=rec)
    off = CNNServingEngine(g, params, None, batch_size=4)
    loop = HostSpans()
    serve(on, rec, imgs, per_tick=3)
    serve(off, loop, imgs, per_tick=3)
    assert off.spans is None
    assert {n for n, _, _ in loop.spans} == {"engine.step"}
    assert len(rec.spans) == 3 * 4 + len(loop.spans)
    assert sorted(on.done) == sorted(off.done) == list(range(7))
    for rid in range(7):
        np.testing.assert_array_equal(on.done[rid], off.done[rid])
