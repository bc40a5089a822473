"""The reduction from a profiler trace to device metrics, on a hand-made
trace and on a small trace recorded on a TPU v5e."""
import dataclasses
import gzip
from pathlib import Path
from typing import List

import pytest

from bench import devtrace

# Five batch-1 GoogleNet-224 ticks of the served path, recorded on one
# TPU v5e (the benchmark's spans around engine calls, profiler defaults).
FIXTURE = Path(__file__).parent / "data" / "googlenet-224-b1.xplane.pb.gz"


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclasses.dataclass
class Line:
    name: str
    events: List[Ev]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


@dataclasses.dataclass
class Trace:
    planes: List[Plane]


CALL = ' = f32[8] custom-call(...), custom_call_target="tpu_custom_call"'


def hand_trace():
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.window", 1000, 1000),
        Ev("client.submit", 1000, 50),
        Ev("engine.step", 1050, 700),
        Ev("client.collect", 1750, 100),
        Ev("client.wait", 1850, 150),
    ])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_run", 1100, 600)]),
        Line("XLA Ops", [
            Ev("copy.1", 900, 200),                 # starts before window
            Ev("%vmap_jit_conv_im2col__.1" + CALL, 1100, 300),
            Ev("%fusion.2 = f32[8] fusion(...)", 1300, 200),  # overlaps
            Ev("%gemm.3" + CALL, 1600, 100),
            Ev("copy.4", 1950, 100),                # ends after window
        ])])
    other = Plane("/device:TPU:1", [Line("XLA Ops", [Ev("x", 1000, 999)])])
    return Trace([host, dev, other])


def test_union_and_gaps():
    assert devtrace.union_ns([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]
    assert devtrace.gaps_ns([(0, 3), (5, 8)], 0, 10) == [(3, 5), (8, 10)]
    assert devtrace.gaps_ns([], 2, 4) == [(2, 4)]


def test_hand_trace_reduction():
    r = devtrace.reduce(hand_trace(), chips=1)
    assert r.window_s == pytest.approx(1000e-9)
    # Busy inside [1000, 2000]: [1000,1100] [1100,1500] [1600,1700]
    # [1950,2000] -> 100 + 400 + 100 + 50 = 650 ns.
    assert r.busy_s == pytest.approx(650e-9)
    assert r.op_s == pytest.approx(750e-9)
    assert r.kernel_s == pytest.approx(400e-9)
    b = r.breakdown()
    assert b["device_ops"][0] == ["vmap_jit_conv_im2col__.1",
                                  pytest.approx(300e-9)]
    # Gaps: [1500,1600] in engine.step, [1700,1950] split by the middle:
    # its middle 1825 lies in client.collect.
    assert dict(b["idle_gaps"]) == {"engine.step": pytest.approx(100e-9),
                                    "client.collect": pytest.approx(250e-9)}


def test_missing_window_or_device_is_an_error():
    t = hand_trace()
    t.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="bench.window"):
        devtrace.reduce(t, chips=1)
    with pytest.raises(ValueError, match="device planes"):
        devtrace.reduce(hand_trace(), chips=3)


def test_kernel_classification():
    assert devtrace.is_kernel("%gemm.3" + CALL)
    assert not devtrace.is_kernel("%fusion.2 = f32[8] fusion(...)")
    gather = '%custom-call.1 = s32[7] custom-call(...), ' \
        'custom_call_target="GatherScatterIndicesBitpacked"'
    assert not devtrace.is_kernel(gather)
    assert devtrace.op_name("%gemm.3" + CALL) == "gemm.3"


@pytest.fixture(scope="module")
def chip_trace():
    from jax.profiler import ProfileData
    with gzip.open(FIXTURE) as f:
        return ProfileData.from_serialized_xspace(f.read())


def test_recorded_chip_trace(chip_trace):
    r = devtrace.reduce(chip_trace, chips=1)
    # Five ticks in a 51 ms window; the device works 0.8 ms of each.
    assert r.window_s == pytest.approx(0.0513158, rel=1e-5)
    assert r.busy_s == pytest.approx(0.0040174, rel=1e-4)
    assert r.busy_s <= r.op_s + 1e-12
    ops = devtrace.device_ops(chip_trace, 1)[0]
    assert len(ops) == 5120
    kernels = {o.name.rsplit(".", 1)[0] for o in ops if o.kernel}
    assert kernels == {"gemm", "vmap_jit_conv_im2col__"}
    # 57 convs a tick: conv1 through the im2col kernel, the rest as GEMMs.
    assert sum(o.kernel for o in ops) == 5 * 57
    assert 0.4 < r.kernel_s / r.op_s < 0.6
    b = r.breakdown()
    assert b["device_ops"][0][0] == "vmap_jit_conv_im2col__.1"
    assert len(b["device_ops"]) == devtrace.TOP
    assert b["idle_gaps"][0][0] == "engine.step"
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(
        r.window_s - r.busy_s)


def test_loop_spans_align_to_the_recorded_program_runs(chip_trace):
    """The loop's own spans, on another clock, are moved onto the trace's
    by matching each engine step to the program run it launched; the
    reduction then agrees with the one from the trace's host plane."""
    shift = 123_456_789_000.0
    host = [(n, s - shift, e - shift)
            for n, s, e in devtrace._host_spans(chip_trace)]
    steps = sorted((s, e) for n, s, e in host if n == "engine.step")
    lo, hi = devtrace.align_offset(chip_trace, 1, steps)
    assert lo <= shift <= hi
    off = (lo + hi) / 2
    moved = [(n, s + off, e + off) for n, s, e in host]
    want = devtrace.reduce(chip_trace, chips=1)
    got = devtrace.reduce(chip_trace, chips=1, spans=moved)
    assert got.op_s == pytest.approx(want.op_s)
    assert got.kernel_s == pytest.approx(want.kernel_s)
    assert got.busy_s == pytest.approx(want.busy_s)
    assert got.window_s == pytest.approx(want.window_s)
    # One step too many, or none, and no offset is claimed.
    assert devtrace.align_offset(chip_trace, 1, steps + [steps[-1]]) is None
    assert devtrace.align_offset(chip_trace, 1, []) is None


def test_hand_trace_program_runs_bound_the_offset():
    t = hand_trace()
    assert devtrace.program_runs(t, 1) == {0: [(1100, 1700)]}
    # The run [1100, 1700] inside the step [1050, 1750] shifted by off:
    # off in [1700 - 1750, 1100 - 1050].
    assert devtrace.align_offset(t, 1, [(1050, 1750)]) == (-50, 50)
    assert devtrace.align_offset(t, 1, [(0, 100)]) is None
