"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

On a TPU the trace holds one plane per chip, ``/device:TPU:<n>``, whose
``XLA Ops`` line has one event per operation that ran on the chip and
whose ``XLA Modules`` line has one per program run. The benchmark's own
spans (``bench.window`` around the measured window; ``client.wait``,
``client.submit``, ``engine.step`` and ``client.collect`` inside it) come
either from the host plane ``/host:CPU``, on the trace's clock, or, where
the host tracer is off, from the loop's own record on the host's
monotonic clock, moved onto the trace's clock by ``align_offset``.

* busy: the union of the device-op intervals inside the window, per chip;
* kernel time: the device time of the overlay's Pallas kernels, which
  XLA runs as ``tpu_custom_call`` custom calls; every other op (fusions,
  copies, transposes, reduce-windows, dots) is glue;
* idle gaps: the intervals of the window in which no op ran, labelled by
  the innermost host span open at the gap's middle.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
HOST_SPANS = ("client.wait", "client.submit", "engine.step",
              "client.collect")
TOP = 10


@dataclasses.dataclass
class Op:
    name: str
    start_ns: float
    end_ns: float
    kernel: bool


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                    # mean over chips
    op_s: float                      # summed over chips
    kernel_s: float                  # summed over chips
    op_time: Dict[str, float]        # op name -> seconds, summed over chips
    idle_by_span: Dict[str, float]   # host span -> idle seconds, chip mean

    def breakdown(self) -> Dict[str, List[Tuple[str, float]]]:
        ops = sorted(self.op_time.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def is_kernel(text: str) -> bool:
    """A Pallas kernel: the TPU trace names an op by its whole HLO
    instruction, and XLA runs each Pallas kernel as a custom call with the
    target ``tpu_custom_call``."""
    return 'custom_call_target="tpu_custom_call"' in text


def op_name(text: str) -> str:
    """``%gemm.58 = f32[...] custom-call(...)`` -> ``gemm.58``: the TPU
    trace names an op by its whole HLO instruction."""
    return text.split(" = ", 1)[0].lstrip("%")


def union_ns(intervals: Iterable[Tuple[float, float]]
             ) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps_ns(busy: List[Tuple[float, float]], lo: float, hi: float
            ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def _host_spans(pd) -> List[Tuple[str, float, float]]:
    spans = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN or ev.name in HOST_SPANS:
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return spans


class _Labeller:
    """The host span open at a time. The loop's spans follow one another
    and do not nest, so the one that started last before ``t`` is the
    only candidate."""

    def __init__(self, spans: List[Tuple[str, float, float]]) -> None:
        inner = sorted((s, e, n) for n, s, e in spans if n != WINDOW_SPAN)
        self.starts = [s for s, _, _ in inner]
        self.inner = inner

    def __call__(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and self.inner[i][1] >= t:
            return self.inner[i][2]
        return "other"


def program_runs(pd, chips: int) -> Dict[int, List[Tuple[float, float]]]:
    """Per chip, the (start, end) of every program run, in order."""
    runs: Dict[int, List[Tuple[float, float]]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) >= chips:
            continue
        lst = runs.setdefault(int(m.group(1)), [])
        for line in plane.lines:
            if line.name == MODULES_LINE:
                lst += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
        lst.sort()
    return runs


def align_offset(pd, chips: int, steps: List[Tuple[float, float]]):
    """The range (lo, hi) of offsets ``off`` (trace time = host time +
    off) under which every chip's n-th program run lies inside the n-th
    ``engine.step`` span, or None where the counts differ or no offset
    fits. The engine runs one program per step and waits for it, so each
    run lies inside the step that launched it."""
    lo, hi = -float("inf"), float("inf")
    runs = program_runs(pd, chips)
    if len(runs) != chips:
        return None
    for chip_runs in runs.values():
        if len(chip_runs) != len(steps) or not steps:
            return None
        for (rs, re_), (s0, s1) in zip(chip_runs, steps):
            lo, hi = max(lo, re_ - s1), min(hi, rs - s0)
    return (lo, hi) if lo <= hi else None


def device_ops(pd, chips: int) -> Dict[int, List[Op]]:
    ops: Dict[int, List[Op]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) >= chips:
            continue
        lst = ops.setdefault(int(m.group(1)), [])
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                lst.append(Op(op_name(ev.name), ev.start_ns,
                              ev.start_ns + ev.duration_ns,
                              is_kernel(ev.name)))
    return ops


def reduce(pd, chips: int, spans=None) -> Reduced:
    """``spans``: the benchmark's (name, start, end) on the trace's clock;
    by default those on the trace's host plane."""
    spans = _host_spans(pd) if spans is None else spans
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    _, lo, hi = windows[0]
    label = _Labeller(spans)
    per_chip = device_ops(pd, chips)
    if len(per_chip) != chips:
        raise ValueError(f"trace has device planes {sorted(per_chip)}, "
                         f"expected {chips}")
    busy_total = op_s = kernel_s = 0.0
    op_time: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    for chip_ops in per_chip.values():
        clipped = [(max(o.start_ns, lo), min(o.end_ns, hi), o)
                   for o in chip_ops if o.end_ns > lo and o.start_ns < hi]
        busy = union_ns((s, e) for s, e, _ in clipped)
        busy_total += sum(e - s for s, e in busy)
        for s, e, o in clipped:
            op_s += e - s
            op_time[o.name] += (e - s) * 1e-9
            if o.kernel:
                kernel_s += e - s
        for s, e in gaps_ns(busy, lo, hi):
            idle[label((s + e) / 2)] += (e - s) * 1e-9 / chips
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=busy_total * 1e-9 / chips,
                   op_s=op_s * 1e-9, kernel_s=kernel_s * 1e-9,
                   op_time=dict(op_time), idle_by_span=dict(idle))


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def find_xplane(logdir: str) -> str:
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {logdir}, "
                                f"found {found}")
    return found[0]


def reduce_dir(logdir: str, chips: int) -> Reduced:
    return reduce(load(find_xplane(logdir)), chips)
