r"""The serving engine's own spans, read on the host and against the device
trace.

    python3 bench/engine_spans.py --workload googlenet224-b1 \
        --seed 11 --seed 12 --seconds 10 --trace 1

runs a cell as ``bench/run.py`` does, except that one recorder
(``repro.serving.spans.HostSpans``) goes to the load loop and to the
engine (``CNNServingEngine(spans=...)``), so the engine's tick spans
(``engine.stage``, ``engine.launch``, ``engine.block``, ``engine.unpack``)
nest inside the loop's ``engine.step``. One engine serves one window per
``--seed``: the first seed makes the weights and the image pool, each
seed its window's traffic. Each window prints one JSON line:

* ``untraced``: the spans from the window's start to the moment the
  profiler starts (the whole window with ``--trace 0``, or where the
  window is no longer than ``harness.TRACE_S``), as the untraced run sees
  the cell: per span name the count, total and self milliseconds, the
  quantities ``tick_host_ms``, ``launch_ms`` and ``step_max_ms``, and the
  longest steps split by their children;
* ``traced``: the same over the profiler's slice (``--trace 1``);
* ``align`` (``--trace 1``): how tightly the spans sit on the trace's
  clock, matching the n-th program run to the n-th ``engine.step``, and
  to the n-th [``engine.launch`` start, ``engine.block`` end];
* ``idle_gaps`` (``--trace 1``): the device's idle time in the slice by
  the innermost span open at each gap's middle;
* ``checks``: the served logits against the reference, as in a run.

The functions are what reads the engine's spans; the command needs a TPU.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import sys
import tempfile
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from bench import devtrace  # noqa: E402

Span = Tuple[str, int, int]
STEP, LAUNCH, BLOCK = "engine.step", "engine.launch", "engine.block"
# The recorder's bound in a run: far more spans than a window records.
SPAN_BOUND = 1 << 20
LONG_STEPS = 5


def within(spans: Sequence[Span], lo: float, hi: float) -> List[Span]:
    """The spans that lie wholly inside [lo, hi]."""
    return [sp for sp in spans if lo <= sp[1] and sp[2] <= hi]


def _nest(spans: Sequence[Span]) -> Tuple[List[int], List[int]]:
    """Span indices sorted by start (the longer first on a tie), and each
    span's parent, the innermost other span that holds it (-1 for none).
    Spans recorded by one thread nest: each lies inside another or
    follows it."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][1], -spans[i][2]))
    parent = [-1] * len(spans)
    stack: List[int] = []
    for i in order:
        while stack and spans[stack[-1]][2] < spans[i][2]:
            stack.pop()
        if stack:
            parent[i] = stack[-1]
        stack.append(i)
    return order, parent


def self_ns(spans: Sequence[Span]) -> List[int]:
    """Each span's length minus the part of it its children cover."""
    _, parent = _nest(spans)
    out = [e - s for _, s, e in spans]
    for i, p in enumerate(parent):
        if p >= 0:
            out[p] -= spans[i][2] - spans[i][1]
    return out


def summary(spans: Sequence[Span]) -> Dict[str, Dict[str, float]]:
    """Per span name: ``n``, ``total_ms`` and ``self_ms``."""
    out: Dict[str, Dict[str, float]] = {}
    for (name, s, e), own in zip(spans, self_ns(spans)):
        row = out.setdefault(name, {"n": 0, "total_ms": 0.0, "self_ms": 0.0})
        row["n"] += 1
        row["total_ms"] += (e - s) * 1e-6
        row["self_ms"] += own * 1e-6
    return out


def _durations_ms(spans: Sequence[Span], name: str) -> List[float]:
    return [(e - s) * 1e-6 for n, s, e in spans if n == name]


def tick_host_ms(spans: Sequence[Span]) -> Optional[float]:
    """Mean over ``engine.step`` spans of the step's length minus the
    ``engine.block`` spans inside it: the host's part of a tick."""
    steps = [(s, e) for n, s, e in spans if n == STEP]
    if not steps:
        return None
    blocks = sorted((s, e) for n, s, e in spans if n == BLOCK)
    starts = [s for s, _ in blocks]
    host = 0
    for s, e in steps:
        i = bisect.bisect_left(starts, s)
        waited = 0
        while i < len(blocks) and blocks[i][0] <= e:
            if blocks[i][1] <= e:
                waited += blocks[i][1] - blocks[i][0]
            i += 1
        host += e - s - waited
    return host * 1e-6 / len(steps)


def launch_ms(spans: Sequence[Span]) -> Optional[float]:
    """Mean ``engine.launch``."""
    d = _durations_ms(spans, LAUNCH)
    return sum(d) / len(d) if d else None


def step_max_ms(spans: Sequence[Span]) -> Optional[float]:
    """Longest ``engine.step``."""
    return max(_durations_ms(spans, STEP), default=None)


def long_steps(spans: Sequence[Span], n: int = LONG_STEPS) -> List[Dict]:
    """The ``n`` longest ``engine.step`` spans, each with the total
    milliseconds of each span name inside it."""
    steps = sorted((sp for sp in spans if sp[0] == STEP),
                   key=lambda sp: sp[2] - sp[1], reverse=True)[:n]
    out = []
    for _, s, e in steps:
        inside: Dict[str, float] = collections.defaultdict(float)
        for name, a, b in within(spans, s, e):
            if (a, b) != (s, e):
                inside[name] += (b - a) * 1e-6
        out.append({"ms": (e - s) * 1e-6, "inside_ms": dict(inside)})
    return out


def brackets(spans: Sequence[Span], after_ns: float
             ) -> List[Tuple[int, int]]:
    """Per tick launched at or after ``after_ns``: (its ``engine.launch``
    start, its ``engine.block`` end). Both count from the record's start:
    the engine launches its ticks in order and blocks on each once, in the
    same order, so the n-th block is the n-th launch's. A record that has
    dropped spans, or whose engine failed a launch, cannot be paired."""
    launches = [s for n, s, _ in spans if n == LAUNCH]
    blocks = [e for n, _, e in spans if n == BLOCK]
    return [(a, b) for a, b in zip(launches, blocks) if a >= after_ns]


class Labeller:
    """The innermost span open at a time (``devtrace.WINDOW_SPAN``
    aside); "other" where none is. For spans that do not nest it is the
    one that started last, as ``devtrace``'s labeller gives."""

    def __init__(self, spans: Sequence[Span]) -> None:
        inner = [sp for sp in spans if sp[0] != devtrace.WINDOW_SPAN]
        order, parent = _nest(inner)
        rank = {i: k for k, i in enumerate(order)}
        self.starts = [inner[i][1] for i in order]
        self.ends = [inner[i][2] for i in order]
        self.names = [inner[i][0] for i in order]
        self.parent = [rank[parent[i]] if parent[i] >= 0 else -1
                       for i in order]

    def __call__(self, t: float) -> str:
        k = bisect.bisect_right(self.starts, t) - 1
        while k >= 0 and self.ends[k] < t:
            k = self.parent[k]
        return self.names[k] if k >= 0 else "other"


def idle_by_span(pd, chips: int, spans: Sequence[Span]) -> Dict[str, float]:
    """Seconds of the ``devtrace.WINDOW_SPAN`` in which no op ran on a
    chip, by ``Labeller``, averaged over the chips; ``spans`` on the
    trace's clock."""
    windows = [sp for sp in spans if sp[0] == devtrace.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {devtrace.WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    _, lo, hi = windows[0]
    label = Labeller(spans)
    idle: Dict[str, float] = collections.defaultdict(float)
    for ops in devtrace.device_ops(pd, chips).values():
        busy = devtrace.union_ns((max(o.start_ns, lo), min(o.end_ns, hi))
                                 for o in ops
                                 if o.end_ns > lo and o.start_ns < hi)
        for s, e in devtrace.gaps_ns(busy, lo, hi):
            idle[label((s + e) / 2)] += (e - s) * 1e-9 / chips
    return dict(idle)


def part(spans: Sequence[Span], lo: float, hi: float) -> Dict:
    """What the spans inside [lo, hi] say of the ticks there."""
    inside = [sp for sp in within(spans, lo, hi)
              if sp[0] != devtrace.WINDOW_SPAN]
    return {"from_ns": lo, "to_ns": hi, "spans": summary(inside),
            "tick_host_ms": tick_host_ms(inside),
            "launch_ms": launch_ms(inside),
            "step_max_ms": step_max_ms(inside),
            "long_steps": long_steps(inside)}


def _width_us(fit) -> Optional[float]:
    return None if fit is None else (fit[1] - fit[0]) / 1e3


def trace_report(tracer, rec, chips: int, t_end_ns: int) -> Dict:
    """The profiler's slice: both alignments and the labelled idle gaps,
    with the spans moved by the tighter one that fits."""
    pd = devtrace.load(devtrace.find_xplane(tracer.logdir))
    t0, t1 = tracer.started_ns
    spans = list(rec.spans)
    steps = [(a, b) for n, a, b in spans if n == STEP and a >= t1]
    by_step = devtrace.align_offset(pd, chips, steps)
    by_tick = (devtrace.align_offset(pd, chips, brackets(spans, t1))
               if rec.dropped == 0 else None)
    fit = by_tick or by_step
    off = (fit[0] + fit[1]) / 2 if fit is not None else -t0
    host = [(n, a + off, b + off) for n, a, b in spans
            if n != devtrace.WINDOW_SPAN and b > t1]
    host.append((devtrace.WINDOW_SPAN, t1 + off, t_end_ns + off))
    reduced = devtrace.reduce(pd, chips, host)
    gaps = sorted(idle_by_span(pd, chips, host).items(), key=lambda kv: -kv[1])
    return {"align": {"steps": len(steps), "step_us": _width_us(by_step),
                      "launch_block_us": _width_us(by_tick)},
            "busy_s": reduced.busy_s, "slice_s": reduced.window_s,
            "idle_gaps": [[k, v] for k, v in gaps[:devtrace.TOP]]}


def run(cell_name: str, seeds: Sequence[int], seconds: float, trace: bool,
        *, spec: Optional[Dict] = None, config: Optional[Dict] = None,
        require_tpu: bool = True) -> List[Dict]:
    """One window per seed on one engine; a report per window.
    ``config`` and ``require_tpu=False`` are for the tests, as in
    ``harness.run``."""
    from bench import harness, loadgen, reference, system
    from repro.serving.spans import HostSpans

    spec = spec or harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.find_cell(spec, cell_name)
    chips = int(cell["chips"])
    conf_entry = next(c for c in spec["configs"]
                      if c["name"] == cell["config"])
    config = config or harness.load_json(ROOT / conf_entry["file"])
    traffic = harness.load_json(harness.BENCH / "traffic"
                                / f"{cell['traffic']}.json")
    peaks = harness.load_json(harness.BENCH / "peaks.json")
    e2e = {m["name"]: harness.load_reader(m["name"])
           for m in harness.cell_metrics(spec, cell_name, "end_to_end")
           if m["name"] != "setup_s"}

    import jax
    peak = harness.check_devices(chips, peaks) if require_tpu else None
    harness.enable_cache()
    graph = system.build_graph(config)
    params = system.make_params(graph, seeds[0])
    pool = system.make_images(graph, seeds[0], int(traffic["pool_images"]))
    rec = HostSpans(maxlen=SPAN_BOUND)
    engine = system.make_engine(graph, params, system.plan(graph),
                                dict(traffic["engine"], spans=rec), chips)
    ref = reference.logits(graph, params, pool,
                           config["check"]["reference_precision"])
    compiles = harness.CompileCounter()
    runner = loadgen.RUNNERS[traffic["kind"]]
    dev0 = jax.devices()[0]
    reports = []
    for seed in seeds:
        rec.clear()
        with tempfile.TemporaryDirectory(prefix="bench_spans_") as tdir:
            # The harness's profiler, started and stopped as in a run;
            # the spans go to ``rec``, not to the tracer's own recorder.
            tracer = harness.Tracer(tdir, seconds) if trace else None
            compiles.n, compiles.active = 0, True
            try:
                win = runner(engine, pool, traffic, seconds, seed, span=rec,
                             at=tracer.at if trace else None)
            finally:
                compiles.active = False
                if trace:
                    tracer.stop()
            lo, hi = int(win.t0 * 1e9), int(win.t_end * 1e9)
            profiled = tracer.started_ns if trace else None
            cut = profiled[0] if profiled and profiled[0] > lo else hi
            spans = list(rec.spans)
            report = {"workload": cell_name, "seed": seed, "trace": trace,
                      "device": {"platform": dev0.platform,
                                 "kind": dev0.device_kind, "chips": chips},
                      "window_s": win.window_s,
                      "completed": win.completed_in_window,
                      "compiles": compiles.n,
                      "dropped_spans": rec.dropped,
                      "untraced": part(spans, lo, cut)}
            ctx = harness.Ctx(graph=graph, win=win, setup={}, chips=chips,
                              peak=peak)
            report["end_to_end"] = {k: read(ctx) for k, read in e2e.items()}
            if trace:
                report["traced"] = part(spans, profiled[1], hi)
                report.update(trace_report(tracer, rec, chips, hi))
        out, want = harness.served_logits(win, ref)
        report["checks"] = harness.readings(out, want)
        report["checks"]["unanswered"] = win.unanswered
        reports.append(report)
        harness.log(f"{cell_name} seed {seed}: "
                    + json.dumps({k: report["untraced"][k] for k in
                                  ("tick_host_ms", "launch_ms",
                                   "step_max_ms")})
                    + (f" align {json.dumps(report['align'])}"
                       if trace else ""))
        for name, row in sorted(report["untraced"]["spans"].items()):
            harness.log(f"  untraced {name}: n {row['n']}, total "
                        f"{row['total_ms']:.3f} ms, self "
                        f"{row['self_ms']:.3f} ms")
    return reports


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    try:
        reports = run(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for r in reports:
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
