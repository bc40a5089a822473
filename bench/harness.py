"""One run of one cell, driven by ``BENCHMARK.json`` and files found by name.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``). Each metric that the cell
reports is read by its own reader, ``bench/metrics/<metric>.py`` (or, for
``<quantity>.<cell family>``, ``bench/metrics/<quantity>.py``), whose
``read(ctx)`` returns a number, or None where it finds nothing to read.
A new cell, configuration, mix or metric is new files and new entries.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import sys
import tempfile
import time
from contextlib import nullcontext
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# JAX's persistent compilation cache, at a fixed path inside the checkout.
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, fewer chips than the cell asks for, or a device
    the peak table does not know."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def find_cell(spec: Dict, name: str) -> Dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(spec: Dict, cell: str, group: str) -> List[Dict]:
    """The entries of ``end_to_end`` or ``per_layer`` that ``cell``
    reports. A per-layer entry without ``workloads`` goes with every cell
    that reports the end-to-end metric it moves."""
    e2e = [m["name"] for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if group == "end_to_end":
        return [m for m in spec["end_to_end"] if m["name"] in e2e]
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def load_reader(name: str) -> Callable:
    """``bench/metrics/<name>.py``, or else the reader of the quantity
    that ``name`` splits by cell family: ``device_idle.b1`` falls back to
    ``device_idle.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = BENCH / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    if not path.exists():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def check_devices(chips: int, peaks: Dict) -> Dict:
    """The cell's chips and their peaks; raises ``NoChip`` off a TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks["devices"]:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks["devices"][kind]


def enable_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class GcPauses:
    """Python garbage-collector pauses while ``active``: count, total and
    longest, in seconds."""

    def __init__(self) -> None:
        self.active = False
        self.pauses: List[float] = []
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self.active:
            self.pauses.append(time.perf_counter() - self._t)

    def close(self) -> None:
        gc.callbacks.remove(self._on)


class CompileCounter:
    """Counts XLA backend compiles while ``active``."""

    def __init__(self) -> None:
        import jax
        self.active = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and "backend_compile" in event:
            self.n += 1


def trace_options():
    """The device's ops alone: the host tracer records the runtime's own
    events and slows the loop about fourfold, so the loop records its
    spans itself (``loadgen.HostSpans``)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


# The traced slice: the window's last TRACE_S seconds, or all of a shorter
# window. It bounds the trace that the run writes and reads back.
TRACE_S = 2.5


class Tracer:
    """The profiler over the window's last ``TRACE_S`` seconds, and the
    reduction of its trace with the loop's own spans."""

    def __init__(self, logdir: str, seconds: float) -> None:
        from bench import loadgen
        self.logdir = logdir
        self.spans = loadgen.HostSpans()
        self.started_ns: Optional[tuple] = None
        self.at = None
        if seconds <= TRACE_S:
            self.start()
        else:
            self.at = (seconds - TRACE_S, self.start)

    def start(self) -> None:
        import jax
        t = time.monotonic_ns()
        jax.profiler.start_trace(self.logdir,
                                 profiler_options=trace_options())
        self.started_ns = (t, time.monotonic_ns())
        log(f"trace: profiler started in {(self.started_ns[1] - t) / 1e6:.3f}"
            f" ms")

    def stop(self) -> None:
        import jax
        if self.started_ns is not None:
            jax.profiler.stop_trace()

    def reduce(self, chips: int, win):
        """The trace reduced over [profiler started, window's end], with the
        loop's spans moved onto the trace's clock: by matching each
        ``engine.step`` to the program run it launched, or else by the
        profiler's session start, which the trace's times count from."""
        from bench import devtrace
        pd = devtrace.load(devtrace.find_xplane(self.logdir))
        t0, t1 = self.started_ns
        steps = [(a, b) for n, a, b in self.spans.spans
                 if n == "engine.step" and a >= t1]
        fit = devtrace.align_offset(pd, chips, steps)
        if fit is not None:
            off = (fit[0] + fit[1]) / 2
            log(f"trace: {len(steps)} engine steps matched to program runs; "
                f"clock offset known to {(fit[1] - fit[0]) / 1e3:.1f} us")
        else:
            off = -t0
            log(f"trace: {len(steps)} engine steps do not match the program "
                f"runs; clock offset from the profiler's session start")
        end = int(win.t_end * 1e9)
        host = [(n, a + off, b + off) for n, a, b in self.spans.spans
                if n != devtrace.WINDOW_SPAN and b > t1]
        host.append((devtrace.WINDOW_SPAN, t1 + off, end + off))
        return devtrace.reduce(pd, chips, host)

    def completed(self, win) -> int:
        """Requests answered inside the traced slice."""
        lo, hi = self.started_ns[1] * 1e-9, win.t_end
        return sum(1 for t in win.t_done.values() if lo <= t <= hi)


@dataclasses.dataclass
class Ctx:
    """What a metric reader may read."""
    graph: object
    win: object                      # loadgen.Window
    setup: Dict[str, float]
    chips: int
    peak: Optional[Dict[str, float]]
    trace: Optional[object] = None   # devtrace.Reduced, with --trace 1
    traced_done: int = 0             # requests answered in the traced slice


def served_logits(win, ref: np.ndarray):
    """Every answered request's logits and its pool image's reference
    logits, as two (N, classes) float32 arrays."""
    rids = sorted(win.logits)
    out = np.stack([np.asarray(win.logits[r], np.float32).reshape(-1)
                    for r in rids]) if rids else np.zeros((0, ref.shape[1]))
    want = ref[[win.image[r] for r in rids]]
    if out.shape != want.shape:
        raise ValueError(f"served logits {out.shape} vs reference "
                         f"{want.shape}")
    return out.astype(np.float32), want


def bf16_grid_share(out: np.ndarray) -> float:
    """Percent of the logits that bfloat16 holds exactly: float32 values
    whose low 16 bits are zero. Float32 arithmetic lands there about once
    in 65,536; logits stored in bfloat16 always do."""
    if not out.size:
        return float("inf")
    bits = np.ascontiguousarray(out, np.float32).view(np.uint32)
    return 100.0 * float(np.mean((bits & 0xFFFF) == 0))


def readings(out: np.ndarray, want: np.ndarray) -> Dict[str, float]:
    """The numbers that may be compared, over every answered request:
    ``logit_err``, the largest per-request max |served - reference| over
    max |reference|; ``mean_err``, the mean of that per-request number;
    ``rel_l2``, the largest per-request |served - reference|_2 over
    |reference|_2; ``bf16_grid``, ``bf16_grid_share`` of the served
    logits. An empty or non-finite reading is inf."""
    if not out.size:
        return {k: float("inf") for k in
                ("logit_err", "mean_err", "rel_l2", "bf16_grid")}
    d = np.abs(out - want)
    per = np.max(d, axis=1) / np.max(np.abs(want), axis=1)
    l2 = (np.linalg.norm(out - want, axis=1)
          / np.linalg.norm(want, axis=1))
    vals = {"logit_err": float(np.max(per)), "mean_err": float(np.mean(per)),
            "rel_l2": float(np.max(l2)), "bf16_grid": bf16_grid_share(out)}
    return {k: (v if np.isfinite(v) else float("inf"))
            for k, v in vals.items()}


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, spec: Optional[Dict] = None,
        config: Optional[Dict] = None, require_tpu: bool = True) -> Dict:
    """Run one cell and return its result line. ``config`` replaces the
    configuration file and ``require_tpu=False`` skips the look for a
    chip: both are for the tests."""
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    cell = find_cell(spec, cell_name)
    chips = int(cell["chips"])
    conf_entry = next(c for c in spec["configs"]
                      if c["name"] == cell["config"])
    config = config or load_json(ROOT / conf_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    group = "per_layer" if trace else "end_to_end"
    readers = {m["name"]: load_reader(m["name"])
               for m in cell_metrics(spec, cell_name, group)}
    peaks = load_json(BENCH / "peaks.json")

    import jax
    peak = check_devices(chips, peaks) if require_tpu else None
    enable_cache()
    from bench import devtrace, loadgen, reference, system

    setup: Dict[str, float] = {}
    graph = system.build_graph(config)
    t = time.monotonic()
    plan = system.plan(graph)
    setup["plan_s"] = time.monotonic() - t
    params = system.make_params(graph, seed)
    jax.block_until_ready(params)
    pool = system.make_images(graph, seed, int(traffic["pool_images"]))
    t = time.monotonic()
    engine = system.make_engine(graph, params, plan, traffic["engine"], chips)
    setup["compile_s"] = time.monotonic() - t
    compiles = CompileCounter()
    gc_pauses = GcPauses()
    runner = loadgen.RUNNERS[traffic["kind"]]

    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        tracer = Tracer(tdir, seconds) if trace else None
        span = tracer.spans if trace else (lambda name: nullcontext())
        setup["setup_s"] = time.monotonic() - t_start
        compiles.active = gc_pauses.active = True
        try:
            win = runner(engine, pool, traffic, seconds, seed, span=span,
                         at=tracer.at if trace else None)
        finally:
            compiles.active = gc_pauses.active = False
            gc_pauses.close()
            if trace:
                tracer.stop()
        reduced = tracer.reduce(chips, win) if trace else None
        traced_done = tracer.completed(win) if trace else 0

    used = jax.devices()[:chips]
    peaks_mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in used]
    del engine
    gc.collect()
    check = config["check"]
    out, want = served_logits(win, reference.logits(
        graph, params, pool, check["reference_precision"]))
    got = readings(out, want)

    ctx = Ctx(graph=graph, win=win, setup=setup, chips=chips, peak=peak,
              trace=reduced, traced_done=traced_done)
    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    metrics = {}
    for name, read in readers.items():
        value = read(ctx)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": units[name]}

    if win.late_s:
        late = np.asarray(win.late_s) * 1e3
        log(f"generator: submit time minus due time p95 "
            f"{np.percentile(late, 95):.4f} ms, max {late.max():.4f} ms "
            f"over {len(late)} requests")
    log(f"gc in window: {len(gc_pauses.pauses)} pauses, "
        f"{sum(gc_pauses.pauses) * 1e3:.4f} ms in all, longest "
        f"{max(gc_pauses.pauses, default=0.0) * 1e3:.4f} ms")
    log(f"window: {win.completed_in_window} completed in "
        f"{win.window_s:.4f} s, {win.attempted} attempted, "
        f"{win.unanswered} unanswered; buckets dispatched "
        f"{ {b: n for b, n in win.dispatched.items() if n} }; "
        f"compiles in window {compiles.n}")
    log(f"setup: {json.dumps(setup)}")

    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(max(peaks_mem))}
    checks = {name: {"value": got[name], "limit": float(limit)}
              for name, limit in check["limits"].items()}
    checks["unanswered"] = {"value": win.unanswered, "limit": 0}
    result = {"correct": bool(out.size > 0 and all(
                  c["value"] <= c["limit"] for c in checks.values())),
              "attempted": win.attempted, "failed": win.unanswered,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result
