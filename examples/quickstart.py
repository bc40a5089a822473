"""Quickstart: the full DYNAMAP flow on GoogleNet in ~a minute on CPU.

    PYTHONPATH=src python examples/quickstart.py

1. Build the CNN graph (GoogleNet — the paper's first evaluation network).
2. Run Algorithm 1 (hardware DSE → virtual-array shape + per-(layer, algo)
   dataflow).
3. Build the cost graph and solve the PBQP optimally via series-parallel
   reduction (Theorem 4.1).
4. Compare against the paper's fixed-algorithm baselines (Table 4).
5. Execute the network under the chosen plan and check it matches the
   im2col-only reference bit-for-bit semantics.
6. Lower the plan with ``compile_plan`` into ONE jit-compiled, batched
   overlay program (no Python dispatch on the hot path) and serve a batch.
"""
import sys
import time
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import numpy as np

from repro.cnn.executor import compile_plan, forward, init_params
from repro.cnn.models import googlenet
from repro.compile_cache import enable_compile_cache
from repro.core import IM2COL
from repro.core.cost_model import FPGA_LIKE
from repro.core.dse import identify_parameters
from repro.core.graph import is_series_parallel
from repro.core.mapper import evaluate_fixed_mapping, map_network


def main() -> None:
    enable_compile_cache()
    # Reduced spatial size so the executor runs in seconds on CPU; the cost
    # model itself prices the full-size network just as fast.
    g = googlenet(res=56, scale=0.25)
    print(f"GoogleNet graph: {len(g.nodes)} nodes, "
          f"{len(g.conv_nodes())} conv layers, "
          f"series-parallel={is_series_parallel(g)}")

    hw = identify_parameters(g, spec=FPGA_LIKE, max_dim=512, k_panel=256)
    print(f"Algorithm 1 → virtual array ({hw.p1}×{hw.p2}), "
          f"τ_emp={hw.tau_emp * 1e3:.3f} ms")

    plan = map_network(g, hw=hw, spec=FPGA_LIKE)
    print(f"PBQP optimal mapping (exact={plan.solver.exact}): "
          f"{dict(Counter(str(a) for a in plan.assignment.values()))}")
    print(f"end-to-end latency (cost model): {plan.total_cost_s * 1e3:.3f} ms")
    for pol in ("im2col", "kn2row", "winograd"):
        bl = evaluate_fixed_mapping(g, pol, hw=hw, spec=FPGA_LIKE)
        print(f"  vs {pol:8s}-only: {bl * 1e3:8.3f} ms "
              f"(OPT {100 * (1 - plan.total_cost_s / bl):5.1f}% lower)")

    params = init_params(g, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (56, 56, 3))
    ref = forward(g, params, x, default_algo=IM2COL)
    opt = forward(g, params, x, plan=plan)
    err = float(np.max(np.abs(np.asarray(opt) - np.asarray(ref))))
    print(f"plan-executed output vs im2col reference: max|Δ| = {err:.2e}")

    # 6. Plan compilation: every per-layer algorithm + dataflow/(p1, p2)
    # choice is closed over at trace time; the result is one XLA program
    # that accepts (H, W, C) or batched (B, H, W, C) inputs. GoogleNet
    # lowers CONV+bias+ReLU fused ("bias_relu" — init_params created the
    # per-conv biases).
    run = compile_plan(g, plan, epilogue="bias_relu")
    xb = jax.random.normal(jax.random.PRNGKey(2), (8, 56, 56, 3))
    yb = jax.block_until_ready(run(params, xb))       # compile + run
    t0 = time.time()
    jax.block_until_ready(run(params, xb))
    t_comp = time.time() - t0
    t0 = time.time()
    for i in range(xb.shape[0]):
        jax.block_until_ready(forward(g, params, xb[i], plan=plan))
    t_eager = time.time() - t0
    err_b = float(np.max(np.abs(np.asarray(yb[0]) - np.asarray(
        forward(g, params, xb[0], plan=plan)))))
    print(f"compiled batched plan: {yb.shape} in {t_comp * 1e3:.1f} ms vs "
          f"eager per-image loop {t_eager * 1e3:.1f} ms "
          f"({t_eager / t_comp:.1f}x); max|Δ| vs eager = {err_b:.2e}")


if __name__ == "__main__":
    main()
