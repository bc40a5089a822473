"""Reading the engine's own spans: self times and the per-tick quantities,
the innermost-span labeller, the launch/block alignment, and a tiny CPU
cell served with the engine's spans on."""
import gzip
import time
import types

import pytest

from bench import devtrace, engine_spans, harness
from bench.tests import faults
from bench.tests.test_bench_devtrace import (CALL, FIXTURE, Ev, Line, Plane,
                                             Trace)
from repro.serving.spans import HostSpans

MS = 1_000_000

# Two synchronous ticks on the host clock, in nanoseconds.
TICKS = [
    ("engine.stage", 100_000, 200_000),
    ("engine.launch", 200_000, 1_200_000),
    ("engine.block", 1_200_000, 2_000_000),
    ("engine.unpack", 2_000_000, 2_900_000),
    ("engine.step", 0, 3 * MS),
    ("client.collect", 3 * MS, 3_500_000),
    ("engine.stage", 4 * MS, 4_500_000),
    ("engine.launch", 4_500_000, 5_500_000),
    ("engine.block", 5_500_000, 8_500_000),
    ("engine.unpack", 8_500_000, 9 * MS),
    ("engine.step", 4 * MS, 9 * MS),
]

# One tick on the trace's clock, the engine's spans nested in the step.
NESTED = [
    ("bench.window", 1000, 2000),
    ("client.submit", 1000, 1050),
    ("engine.stage", 1055, 1070),
    ("engine.launch", 1070, 1090),
    ("engine.block", 1090, 1715),
    ("engine.unpack", 1715, 1745),
    ("engine.step", 1050, 1760),
    ("client.collect", 1760, 1850),
    ("client.wait", 1850, 2000),
]


def nested_trace():
    host = Plane("/host:CPU", [Line("python", [
        Ev(n, s, e - s) for n, s, e in NESTED])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_run", 1100, 600)]),
        Line("XLA Ops", [
            Ev("copy.1", 1000, 75),
            Ev("%gemm.3" + CALL, 1100, 400),
            Ev("%gemm.4" + CALL, 1600, 100),
            Ev("copy.5", 1740, 8),
            Ev("copy.6", 1758, 192),
        ])])
    return Trace([host, dev])


def test_tick_quantities_on_hand_made_spans():
    assert engine_spans.tick_host_ms(TICKS) == pytest.approx(
        ((3.0 - 0.8) + (5.0 - 3.0)) / 2)
    assert engine_spans.launch_ms(TICKS) == pytest.approx(1.0)
    assert engine_spans.step_max_ms(TICKS) == pytest.approx(5.0)
    s = engine_spans.summary(TICKS)
    assert s["engine.step"] == {"n": 2, "total_ms": pytest.approx(8.0),
                                "self_ms": pytest.approx(0.2)}
    assert s["engine.block"]["self_ms"] == pytest.approx(3.8)
    assert s["client.collect"]["n"] == 1
    first = engine_spans.part(TICKS, 0, 3_500_000)
    assert first["tick_host_ms"] == pytest.approx(2.2)
    assert first["step_max_ms"] == pytest.approx(3.0)
    [longest] = engine_spans.long_steps(TICKS, 1)
    assert longest["ms"] == pytest.approx(5.0)
    assert longest["inside_ms"] == {
        "engine.stage": pytest.approx(0.5), "engine.launch": pytest.approx(1),
        "engine.block": pytest.approx(3), "engine.unpack": pytest.approx(0.5)}


def test_a_block_inside_a_later_step_counts_there():
    """Pipelined: tick 0's block runs inside the second step."""
    spans = [("engine.launch", 0, MS), ("engine.step", 0, 2 * MS),
             ("engine.block", 3 * MS, 4 * MS), ("engine.step", 3 * MS, 5 * MS)]
    assert engine_spans.tick_host_ms(spans) == pytest.approx((2 + 1) / 2)


@pytest.mark.parametrize("read", [engine_spans.tick_host_ms,
                                  engine_spans.launch_ms,
                                  engine_spans.step_max_ms])
def test_no_spans_read_nothing(read):
    assert read([]) is None
    assert read([("client.wait", 0, 5)]) is None


def test_a_gap_takes_the_innermost_span():
    t = nested_trace()
    got = engine_spans.idle_by_span(t, 1, NESTED)
    want = {"engine.launch": 25e-9, "engine.block": 100e-9,
            "engine.unpack": 40e-9, "engine.step": 10e-9,
            "client.wait": 50e-9}
    assert got == pytest.approx(want)
    # devtrace's labeller, which assumes spans do not nest, calls the gap
    # in the step after its children "other".
    old = devtrace.reduce(t, 1, NESTED).idle_by_span
    assert old["other"] == pytest.approx(10e-9)
    assert sum(got.values()) == pytest.approx(sum(old.values()))
    label = engine_spans.Labeller(NESTED)
    assert [label(t) for t in (999, 1052, 1080, 1753, 1800, 2001)] == [
        "other", "engine.step", "engine.launch", "engine.step",
        "client.collect", "other"]


def test_recorded_chip_trace_gaps_are_unchanged():
    from jax.profiler import ProfileData
    with gzip.open(FIXTURE) as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    spans = devtrace._host_spans(pd)
    assert engine_spans.idle_by_span(pd, 1, spans) == pytest.approx(
        devtrace.reduce(pd, 1).idle_by_span)


def test_launch_block_alignment_is_narrower_and_holds_the_offset():
    t = nested_trace()
    shift = 987_654_321
    host = [(n, s - shift, e - shift) for n, s, e in NESTED]
    steps = [(s, e) for n, s, e in host if n == "engine.step"]
    by_step = devtrace.align_offset(t, 1, steps)
    by_tick = devtrace.align_offset(
        t, 1, engine_spans.brackets(host, -float("inf")))
    assert by_step == (shift - 60, shift + 50)
    assert by_tick == (shift - 15, shift + 30)
    assert by_step[1] - by_step[0] > by_tick[1] - by_tick[0]
    # Only ticks launched after the given moment are paired.
    assert engine_spans.brackets(host, 1071 - shift) == []


def test_trace_report_moves_the_spans_by_the_tighter_fit(monkeypatch):
    shift = 5_000
    rec = types.SimpleNamespace(dropped=0, spans=[
        (n, s - shift, e - shift) for n, s, e in NESTED
        if n != "bench.window"])
    monkeypatch.setattr(devtrace, "find_xplane", lambda d: d)
    monkeypatch.setattr(devtrace, "load", lambda p: nested_trace())
    tracer = types.SimpleNamespace(logdir="x",
                                   started_ns=(-shift, 1000 - shift))
    got = engine_spans.trace_report(tracer, rec, 1, 2000 - shift)
    assert got["align"] == {"steps": 1, "step_us": pytest.approx(0.110),
                            "launch_block_us": pytest.approx(0.045)}
    # The offset is the fit's middle, 7.5 ns past the true one: the
    # window [1007.5, 2007.5] then holds 767.5 ns of device ops, and the
    # block, moved to end at 1722.5, holds the gap at 1720 too.
    assert got["busy_s"] == pytest.approx(767.5e-9)
    assert dict(got["idle_gaps"]) == pytest.approx({
        "engine.launch": 25e-9, "engine.block": 140e-9,
        "engine.step": 10e-9, "client.wait": 57.5e-9})


@pytest.mark.parametrize("cell", ["googlenet224-b1", "googlenet224-poisson"])
def test_tiny_cell_with_the_engine_spans_on(monkeypatch, cell):
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    reports = engine_spans.run(
        cell, [2**33 + 11, 7], 0.4, False, spec=spec,
        config=faults.tiny_config("googlenet-224"), require_tpu=False)
    assert [r["seed"] for r in reports] == [2**33 + 11, 7]
    for r in reports:
        assert r["checks"]["logit_err"] < 1e-2
        assert r["checks"]["unanswered"] == 0
        part = r["untraced"]
        assert set(part["spans"]) >= {
            "client.submit", "engine.step", "client.collect",
            "engine.stage", "engine.launch", "engine.block",
            "engine.unpack"}
        ticks = part["spans"]["engine.launch"]["n"]
        assert part["spans"]["engine.block"]["n"] == ticks > 0
        assert 0 < part["launch_ms"] < part["step_max_ms"]
        assert 0 < part["tick_host_ms"] < part["step_max_ms"]
        assert r["dropped_spans"] == 0
        want = {"googlenet224-b1": {"b1_latency_ms"},
                "googlenet224-poisson": {"serve_p50_ms", "images_per_s"}}
        assert set(r["end_to_end"]) == want[cell]
        assert all(v > 0 for v in r["end_to_end"].values())


def test_untraced_part_ends_where_the_profiler_starts():
    rec = HostSpans()
    t0 = time.monotonic_ns()
    for name in ("engine.step", "engine.step"):
        with rec(name):
            time.sleep(0.002)
    cut = rec.spans[0][2]
    assert engine_spans.part(list(rec.spans), t0, cut)["spans"] == {
        "engine.step": {"n": 1, "total_ms": pytest.approx(
            (rec.spans[0][2] - rec.spans[0][1]) * 1e-6),
            "self_ms": pytest.approx(
                (rec.spans[0][2] - rec.spans[0][1]) * 1e-6)}}
