"""Benchmark harness: one module per paper table/figure. Prints
``name,...`` CSV rows (μs-scale latencies are cost-model seconds ×1e6 where
applicable; derived columns documented per module) and lands the same rows
in ``BENCH_RESULTS.json`` at the repo root so the perf trajectory
(e.g. the compiled-plan vs eager-loop wall-clock from bench_e2e) is
machine-readable across PRs.

Every module runs, in this one process, even after another raised; a
module that raised leaves an ``ERROR`` row and makes the harness exit
non-zero.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def main() -> int:
    # bench_sharded_serving simulates 8 devices on the CPU backend; the
    # flag only shapes that backend and must be set before JAX starts.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    import benchmarks.bench_adaptive_serving as bas
    import benchmarks.bench_algorithms as ba
    import benchmarks.bench_chaos_serving as bc
    import benchmarks.bench_dse as bd
    import benchmarks.bench_dynamic_batching as bdb
    import benchmarks.bench_e2e as be
    import benchmarks.bench_fused_autotune as bf
    import benchmarks.bench_layout_elision as bl
    import benchmarks.bench_multi_model as bm
    import benchmarks.bench_pipelined_serving as bp
    import benchmarks.bench_quantized as bq
    import benchmarks.bench_roofline as br
    import benchmarks.bench_sharded_serving as bs
    import benchmarks.bench_utilization as bu

    results = {}
    failed = []
    for name, mod in (("bench_algorithms", ba), ("bench_utilization", bu),
                      ("bench_dse", bd), ("bench_e2e", be),
                      ("bench_fused_autotune", bf),
                      ("bench_layout_elision", bl),
                      ("bench_quantized", bq),
                      ("bench_dynamic_batching", bdb),
                      ("bench_sharded_serving", bs),
                      ("bench_pipelined_serving", bp),
                      ("bench_chaos_serving", bc),
                      ("bench_multi_model", bm),
                      ("bench_adaptive_serving", bas),
                      ("bench_roofline", br)):
        t0 = time.time()
        try:
            rows = mod.run()
        except Exception as e:  # keep the harness running end to end
            rows = [f"{name},ERROR,{e!r}"]
            failed.append(name)
        elapsed = time.time() - t0
        results[name] = {"elapsed_s": round(elapsed, 1), "rows": rows}
        print(f"# === {name} ({elapsed:.1f}s) ===")
        print("\n".join(rows))
        print()

    out = REPO / "BENCH_RESULTS.json"
    out.write_text(json.dumps(results, indent=2))
    print(f"# wrote {out}")
    if failed:
        print(f"# FAILED: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
