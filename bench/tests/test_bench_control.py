"""The controls at a tiny size on the CPU: the served path passes the
configuration's limits, and the reference in bfloat16 (one step below the
stated float32) and in int8, put in the program's place, fail them."""
import pytest

from bench import control, harness, system
from bench.tests import faults


@pytest.mark.parametrize("name", ["googlenet-224", "inception_v4-299"])
def test_int8_control_fails_the_limit(name):
    config = faults.tiny_config(name)
    check = config["check"]
    traffic = harness.load_json(harness.BENCH / "traffic" / "closed1.json")
    graph = system.build_graph(config)
    row, _ = control.read_seed(graph, system.plan(graph), traffic, check,
                               seed=2**32 + 5, chips=1)
    limits = check["limits"]

    def fails(reading):
        return any(reading[k] > v for k, v in limits.items())

    assert not fails(row["program"]), row["program"]
    assert fails(row["int8"]) and fails(row["bf16"])
    assert row["int8"]["logit_err"] > limits["logit_err"]
    assert row["bf16"]["bf16_grid"] == 100.0 > limits["bf16_grid"]
    assert row["bf16_vs_highest"] > row["program_vs_highest"]


def test_readings_of_bf16_logits():
    import numpy as np
    rng = np.random.default_rng(2**33)
    want = rng.standard_normal((4, 1000)).astype(np.float32)
    exact = harness.readings(want.copy(), want)
    assert exact["logit_err"] == exact["rel_l2"] == 0.0
    assert exact["bf16_grid"] < 0.1
    import jax.numpy as jnp
    low = np.asarray(jnp.asarray(want, jnp.bfloat16), np.float32)
    r = harness.readings(low, want)
    assert r["bf16_grid"] == 100.0
    assert 0 < r["mean_err"] <= r["logit_err"] < 2.0**-8
    assert harness.readings(np.zeros((0, 3)), want[:0])["logit_err"] == \
        float("inf")
