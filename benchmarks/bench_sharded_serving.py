"""Mesh-sharded serving benchmark (PR 5): multi-chip batch-dim scaling.

Exercises ``CNNServingEngine``'s mesh mode on 1 / 2 / 4 / 8 simulated
devices (``compile_plan(..., mesh=...)`` — params replicated, batch dim on
the mesh's data axis, bucket ladder in multiples of the shard count,
tuning looked up at the *per-chip* batch). Three row groups:

* ``equiv`` — at every device count and every bucket of its ladder, the
  sharded compiled plan's outputs are allclose to the single-device
  program under the SAME lowering (placement changes, math does not).
  This is the hard gate, enforced on every run including ``--smoke``.
* ``replay`` — the PR-3 Poisson arrival trace replayed through each
  sharded engine (same trace, same seed, offered at 0.6x the
  single-device saturation rate): per-device p50/p99 latency, served
  throughput and the bucket dispatch histogram. The committed
  ``throughput_monotonic_1_2_4`` gate asserts replayed throughput is
  non-decreasing 1→2→4 devices within the 10% noise envelope the layout
  bench established for shared-CPU hosts — on this host the 8 simulated
  chips share two physical cores, so the *true* scaling curve is flat
  (total FLOP rate is fixed no matter how the batch is placed); the
  gate proves sharded placement sustains the same offered load with no
  sharding tax, and leaves real speedups to real multi-chip hardware
  (ROADMAP's TPU item).
* ``scaling`` — descriptive: top-bucket tick wall clock per device
  count, measured interleaved (``_timing.sampled_interleaved``) so
  ambient load drift hits every mesh equally, with median *paired*
  per-rep tick ratios in the summary. Raw multi-device dispatch latency
  on an oversubscribed 2-core host is scheduling-luck-bimodal at d >= 4,
  which is exactly why the gate lives on the end-to-end replay instead.

Devices are simulated on CPU via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``, which must be set
before the XLA backend initializes: the CI sharded-smoke job sets it in its
environment, and ``benchmarks/run.py`` sets it for its own process before
any benchmark touches JAX. The benchmark runs in the calling process and
starts no other, so it never competes with its parent for a chip.

``--smoke`` (CI) runs the tiny-graph variant and gates only equivalence.
"""

from __future__ import annotations

import sys
from pathlib import Path
from typing import Dict, List

REPO = Path(__file__).resolve().parents[1]
for _p in (str(REPO), str(REPO / "src")):     # direct `python benchmarks/…`
    if _p not in sys.path:
        sys.path.insert(0, _p)

N_SIM_DEVICES = 8
DEVICE_COUNTS = (1, 2, 4, 8)
# Same 10% envelope (and rationale) as bench_layout_elision's no_slower:
# same-program process-to-process variance exceeds 5% on shared-CPU hosts,
# so tighter margins would gate on scheduling luck.
MONOTONIC_ENVELOPE = 0.90


# ---------------------------------------------------------------------------
# Measurement (runs with 8 simulated devices).
# ---------------------------------------------------------------------------

def _measure(smoke: bool) -> List[str]:
    import jax
    import numpy as np

    from benchmarks._timing import sampled_interleaved
    from benchmarks._trace import hist as _hist
    from benchmarks._trace import poisson_trace as _poisson_trace
    from benchmarks._trace import replay as _replay
    from repro.cnn.executor import compile_plan, init_params
    from repro.cnn.models import googlenet, vgg16
    from repro.core.autotune import autotune_buckets
    from repro.core.dse import identify_parameters
    from repro.core.mapper import map_network
    from repro.launch.mesh import make_data_mesh
    from repro.serving.cnn_engine import CNNServingEngine, batch_buckets

    assert jax.device_count() >= N_SIM_DEVICES, (
        f"need {N_SIM_DEVICES} devices, got {jax.device_count()} — set "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8")

    if smoke:
        tag, g = "vgg16_r8_smoke", vgg16(res=8, scale=0.05)
        plan, record, n_requests, reps = None, None, 24, 3
    else:
        tag, g = "googlenet_r56", googlenet(res=56, scale=0.25)
        hw = identify_parameters(g, max_dim=512)
        plan = map_network(g, hw=hw)
        # Per-chip tuning: sharded buckets look up bucket // data_shards,
        # so the PR-3 ladder {1, 2, 4, 8} covers every per-chip batch any
        # device count below induces.
        record = autotune_buckets(g, plan, buckets=(1, 2, 4, 8),
                                  backends=("lax", "reference"), reps=1)
        # 2x the PR-3 trace length: throughput = served / makespan, so a
        # longer replay tightens the gated estimate.
        n_requests, reps = 192, 15

    batch = 8
    params = init_params(g, jax.random.PRNGKey(0))
    shape = tuple(g.nodes[g.source()].attrs["out_shape"])
    rows = [
        f"sharded_serving,{tag},config,-,devices_available,"
        f"{jax.device_count()}",
        f"sharded_serving,{tag},config,-,batch,{batch}",
    ]

    # ---- equivalence: sharded vs single-device, per bucket -------------
    # The reference is the UNSHARDED program under the same per-chip
    # lowering, so any mismatch is a placement bug, not a binding change.
    rng = np.random.default_rng(3)
    meshes = {d: make_data_mesh(d) for d in DEVICE_COUNTS}
    ref_runs: Dict[int, object] = {}
    top_runs: Dict[int, object] = {}
    all_ok = True
    for d in DEVICE_COUNTS:
        ladder = batch_buckets(batch, d)
        ok = True
        for bucket in ladder:
            per_chip = bucket // d
            if per_chip not in ref_runs:
                ref_runs[per_chip] = compile_plan(
                    g, plan, tuning=record, tuning_batch=per_chip)
            run_m = compile_plan(g, plan, tuning=record,
                                 tuning_batch=per_chip, mesh=meshes[d])
            if bucket == batch:
                top_runs[d] = run_m
            xb = rng.standard_normal((bucket,) + shape).astype(np.float32)
            y_m = np.asarray(run_m(params, xb))
            y_s = np.asarray(ref_runs[per_chip](params, xb))
            ok &= bool(np.allclose(y_m, y_s, rtol=1e-4, atol=1e-5))
        all_ok &= ok
        rows.append(f"sharded_serving,{tag},devices_{d},equiv,"
                    f"buckets,{'|'.join(str(b) for b in ladder)}")
        rows.append(f"sharded_serving,{tag},devices_{d},equiv,outputs_ok,{ok}")

    # ---- throughput scaling: interleaved top-bucket ticks --------------
    xb = rng.standard_normal((batch,) + shape).astype(np.float32)
    fns = {d: (lambda r=top_runs[d]: r(params, xb)) for d in DEVICE_COUNTS}
    samples = sampled_interleaved(fns, reps=reps)
    for d in DEVICE_COUNTS:
        t_min = min(samples[d])
        pre = f"sharded_serving,{tag},devices_{d},scaling"
        rows.append(f"{pre},tick_ms,{t_min * 1e3:.2f}")
        rows.append(f"{pre},throughput_rps,{batch / t_min:.2f}")
    tick_ratios = {}
    for a, b in ((1, 2), (2, 4), (4, 8)):
        # Throughput ratio b-over-a = paired tick-time ratio a-over-b.
        paired = [sa / sb for sa, sb in zip(samples[a], samples[b])]
        tick_ratios[(a, b)] = float(np.median(paired))

    # ---- Poisson replay per device count (the gated rows) --------------
    eng1 = CNNServingEngine(g, params, plan, batch_size=batch,
                            tuning=record, mesh=meshes[1], warmup=True)
    svc8 = eng1.service_estimate(batch)
    rate = 0.6 * batch / svc8
    trace = _poisson_trace(rate, n_requests, shape, seed=42)
    rows.append(f"sharded_serving,{tag},config,-,arrival_rps,{rate:.2f}")
    tput = {}
    for d in DEVICE_COUNTS:
        eng = eng1 if d == 1 else CNNServingEngine(
            g, params, plan, batch_size=batch, tuning=record,
            mesh=meshes[d], warmup=True)
        lat, makespan = _replay(eng, trace)
        st = eng.stats()
        assert st["sharding"]["data_shards"] == d
        tput[d] = len(lat) / makespan
        pre = f"sharded_serving,{tag},devices_{d},replay"
        rows.append(f"{pre},p50_ms,{float(np.percentile(lat, 50)) * 1e3:.2f}")
        rows.append(f"{pre},p99_ms,{float(np.percentile(lat, 99)) * 1e3:.2f}")
        rows.append(f"{pre},throughput_rps,{tput[d]:.2f}")
        rows.append(f"{pre},served,{len(lat)}")
        rows.append(f"{pre},dispatch_hist,{_hist(eng)}")
        rows.append(f"{pre},per_chip_batch_max,{batch // d}")

    mono = (tput[2] >= MONOTONIC_ENVELOPE * tput[1]
            and tput[4] >= MONOTONIC_ENVELOPE * tput[2])
    for a, b in ((1, 2), (2, 4), (4, 8)):
        rows.append(f"sharded_serving,{tag},summary,-,"
                    f"tput_ratio_{b}_over_{a},{tput[b] / tput[a]:.3f}")
        rows.append(f"sharded_serving,{tag},summary,-,"
                    f"tick_tput_ratio_{b}_over_{a},"
                    f"{tick_ratios[(a, b)]:.3f}")
    rows.append(f"sharded_serving,{tag},summary,-,outputs_ok,{all_ok}")
    rows.append(f"sharded_serving,{tag},summary,-,"
                f"throughput_monotonic_1_2_4,{mono}")
    return rows


def run(smoke: bool = False) -> List[str]:
    return _measure(smoke)


if __name__ == "__main__":
    smoke = "--smoke" in sys.argv
    out = run(smoke)
    print("\n".join(out))
    # Equivalence gates every invocation; the throughput-scaling summary is
    # only enforced for the committed full-run rows (CI schema guard) —
    # smoke-scale graphs are too noisy to assert scaling on.
    if any(row.endswith("outputs_ok,False") for row in out):
        sys.exit(1)
