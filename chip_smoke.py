"""On-chip smoke test of the DYNAMAP serving path.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the data-parallel mesh path only

The default run drives the main path through the entry points a user calls,
at the published network sizes, with weights and inputs made from a seed:

* GoogleNet at 224 (width 1.0): ``identify_parameters`` + ``map_network``
  plan it, and ``CNNServingEngine(use_pallas=True)`` serves 12 requests on
  the Pallas overlay kernels, so buckets 8 and 4 both dispatch.
* Inception-v4 at 299 (width 1.0): ``compile_plan(use_pallas=True)`` runs
  once on one image; its plan puts im2col, kn2row and Winograd on the chip.

``--four-chips`` serves GoogleNet-224 through
``CNNServingEngine(mesh=make_data_mesh(4))`` on the 4-multiple bucket
ladder and shows where the shards of one batch live.

Every output is compared with a plain f32 reference on the same inputs and
params. Each phase prints its compile seconds, request latencies, error
against the reference and the number of ``tpu_custom_call`` kernels in each
executable. The last line is ``{"ok": true, "device": {...}}``. The script
exits non-zero, printing no such line, when JAX finds no TPU or when any
phase fails. Latencies here are one cold run's host-clock readings, not a
benchmark.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from collections import Counter
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

SEED = 0
N_REQUESTS = 12
# Normalised max |Δ| (max |out - ref| / max |ref|) allowed against the f32
# reference. The served path runs at the TPU's default matmul precision,
# which rounds f32 operands to bf16 (2^-9 relative) in every layer; over
# the 20-40 sequential layers of these networks that compounds to a few
# percent at most, while a wrong window, tap or tile gives errors of
# order one.
TOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def seeded_params(graph):
    """``init_params`` weights plus seeded nonzero biases, so the fused
    bias epilogue does real work."""
    import jax
    import jax.numpy as jnp
    from repro.cnn.executor import init_params
    params = init_params(graph, jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(SEED)
    for p in params.values():
        if "b" in p:
            p["b"] = jnp.asarray(0.1 * rng.standard_normal(p["b"].shape),
                                 jnp.float32)
    return params


def seeded_images(graph, n: int) -> np.ndarray:
    shape = tuple(graph.nodes[graph.source()].attrs["out_shape"])
    rng = np.random.default_rng(SEED + 1)
    return rng.standard_normal((n,) + shape).astype(np.float32)


def reference(graph, params, x) -> np.ndarray:
    """Plain f32 reference: the same graph walk with every conv on
    ``lax.conv_general_dilated`` (``apply_conv``'s lax backend) and every
    matmul at full f32 precision."""
    import jax
    from repro.cnn.executor import _eval_graph
    from repro.core.mapper import lower_plan
    lowering = lower_plan(graph, None, backend="lax", epilogue="bias_relu")
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(lambda p, xx: _eval_graph(graph, lowering, p, xx,
                                               False, None))
        return np.asarray(fn(params, x))


def check(tag: str, out: np.ndarray, ref: np.ndarray) -> None:
    if out.shape != ref.shape:
        fail(f"{tag}: output shape {out.shape} != reference {ref.shape}")
    err = float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))
    log(f"{tag}: normalised max|d| vs f32 reference = {err:.3e} "
        f"(tolerance {TOL:g})")
    if not np.isfinite(err) or err > TOL:
        fail(f"{tag}: error {err} above tolerance {TOL}")


def count_kernels(tag: str, lowered) -> None:
    # Layers of one shape share one lowered kernel, so this counts the
    # distinct overlay kernels of the program.
    n = lowered.as_text().count("tpu_custom_call")
    log(f"{tag}: executable holds {n} distinct tpu_custom_call kernels")
    if n == 0:
        fail(f"{tag}: no overlay kernel in the executable")


def plan_for(tag: str, graph):
    from repro.core.dse import identify_parameters
    from repro.core.mapper import map_network
    t0 = time.perf_counter()
    hw = identify_parameters(graph)
    plan = map_network(graph, hw=hw)
    algos = dict(Counter(a.key for a in plan.assignment.values()))
    log(f"{tag}: plan p1={hw.p1} p2={hw.p2} {algos} "
        f"({time.perf_counter() - t0:.2f} s)")
    return plan


def serve(tag: str, graph, params, plan, mesh=None):
    """Serve N_REQUESTS seeded requests; returns the engine and the bucket
    ladder it compiled from the same options."""
    from repro.serving.cnn_engine import CNNRequest, CNNServingEngine
    t0 = time.perf_counter()
    engine = CNNServingEngine(graph, params, plan, use_pallas=True,
                              batch_size=8, epilogue="bias_relu", mesh=mesh,
                              warmup=True)
    log(f"{tag}: buckets {engine.buckets} compiled and warmed in "
        f"{time.perf_counter() - t0:.1f} s")
    ladder = engine.compile_ladder(plan, warm=False)
    images = seeded_images(graph, N_REQUESTS)
    for b, run in ladder.items():
        count_kernels(f"{tag} bucket {b}",
                      run.lower(engine.params, images[:1].repeat(b, 0)))
    for rid, img in enumerate(images):
        engine.submit(CNNRequest(rid=rid, image=img))
    done = engine.run_until_done()
    dispatches = engine.stats()["dispatches"]
    lat = [round(t.latency_s * 1e3, 2) for t in engine.request_log]
    log(f"{tag}: dispatches per bucket {dispatches}")
    log(f"{tag}: request latencies ms {lat}")
    if len(done) != N_REQUESTS:
        fail(f"{tag}: served {len(done)} of {N_REQUESTS} requests")
    if not (dispatches.get(8) and any(dispatches.get(b)
                                      for b in engine.buckets if b <= 4)):
        fail(f"{tag}: buckets 8 and 4 (or smaller) did not both dispatch")
    out = np.stack([np.asarray(done[rid]) for rid in range(N_REQUESTS)])
    check(tag, out, reference(graph, params, images))
    return engine, ladder


def inception_once() -> None:
    from repro.cnn.executor import compile_plan
    from repro.cnn.models import inception_v4
    tag = "inception_v4-299"
    g = inception_v4(res=299, scale=1.0)
    plan = plan_for(tag, g)
    params = seeded_params(g)
    x = seeded_images(g, 1)[0]
    run = compile_plan(g, plan, use_pallas=True, epilogue="bias_relu")
    count_kernels(tag, run.lower(params, x))
    t0 = time.perf_counter()
    y = np.asarray(run(params, x))
    log(f"{tag}: first call (compile + run) {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    np.asarray(run(params, x))
    log(f"{tag}: second call {1e3 * (time.perf_counter() - t0):.2f} ms")
    check(tag, y, reference(g, params, x))


def one_chip() -> None:
    from repro.cnn.models import googlenet
    tag = "googlenet-224"
    g = googlenet(res=224, scale=1.0)
    plan = plan_for(tag, g)
    serve(tag, g, seeded_params(g), plan)
    inception_once()


def four_chips() -> None:
    import jax
    from repro.cnn.models import googlenet
    from repro.launch.mesh import make_data_mesh
    if len(jax.devices()) < 4:
        fail(f"--four-chips needs 4 devices, JAX found {len(jax.devices())}")
    tag = "googlenet-224 mesh4"
    g = googlenet(res=224, scale=1.0)
    plan = plan_for(tag, g)
    params = seeded_params(g)
    engine, ladder = serve(tag, g, params, plan, mesh=make_data_mesh(4))
    # Where one top-bucket batch lives: every device must hold a shard of
    # the output, computed by its own slice of the batch.
    images = seeded_images(g, 8)
    out = ladder[8](engine.params, images)
    jax.block_until_ready(out)
    shards = sorted(out.addressable_shards, key=lambda s: s.device.id)
    ref = reference(g, params, images)
    for s in shards:
        rows = s.index[0]
        mem = (s.device.memory_stats() or {}).get("bytes_in_use")
        log(f"{tag}: device {s.device.id} holds output rows "
            f"{rows.start}:{rows.stop}, bytes in use {mem}")
        check(f"{tag} device {s.device.id} shard",
              np.asarray(s.data), ref[rows])
    used = {s.device.id for s in shards if s.data.shape[0] > 0}
    if len(used) != 4:
        fail(f"{tag}: output shards on devices {sorted(used)}, not 4")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip data-parallel mesh path")
    args = ap.parse_args()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    log(f"chip_smoke: {len(jax.devices())} x {dev.device_kind}, "
        f"compile cache {enable_compile_cache()}")
    four_chips() if args.four_chips else one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
