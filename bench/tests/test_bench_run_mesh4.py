"""The four-chip cell's whole run on four virtual CPU devices at a tiny
size: a sound run is correct, and one whose output gather leaves out the
other chips' shards is not."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness

SCRIPT = r"""
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}]
import jax, jax.numpy as jnp
assert len(jax.devices()) == 4, jax.devices()
from bench import harness
from bench.tests import faults
if {fault!r} == "gather":
    from repro.serving import cnn_engine
    real = cnn_engine.compile_plan
    def compile_plan(*a, **kw):
        run = real(*a, **kw)
        def first_shard_only(params, x):
            out = run(params, x)
            return out.at[out.shape[0] // 4:].set(0)
        return first_shard_only
    cnn_engine.compile_plan = compile_plan
harness.enable_cache = lambda: None
spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
cell = "googlenet224-mesh4-saturate"
config = faults.tiny_config(harness.find_cell(spec, cell)["config"])
r = harness.run(cell, 2**33 + 13, 0.5, False, t_start=time.monotonic(),
                spec=spec, config=config, require_tpu=False)
print(json.dumps(r))
"""


@pytest.mark.parametrize("fault,correct", [(None, True), ("gather", False)])
def test_mesh_cell_on_four_cpu_devices(fault, correct):
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    harness.find_cell(spec, "googlenet224-mesh4-saturate")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SCRIPT.format(root=str(harness.ROOT),
                         src=str(harness.ROOT / "src"), fault=fault)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is correct, result["checks"]
    assert result["device"]["count"] == 4
