"""The benchmark's own reference against the program's lax path, at tiny
sizes on the CPU."""
import jax
import numpy as np
import pytest

from bench import reference, system
from repro.cnn import models

TINY = {
    "googlenet": dict(res=32, scale=0.125, classes=10),
    "inception_v4": dict(res=75, scale=0.125, classes=10),
    "resnet18": dict(res=32, scale=0.125, classes=10),
}


def program_lax(graph, params, x):
    from repro.cnn.executor import _eval_graph
    from repro.core.mapper import lower_plan
    lowering = lower_plan(graph, None, backend="lax", epilogue="bias_relu")
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(lambda p, xx: _eval_graph(graph, lowering, p, xx,
                                               False, None))
        return np.asarray(fn(params, x)).reshape(len(x), -1)


def norm_err(a, b):
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


@pytest.mark.parametrize("builder", sorted(TINY))
def test_reference_matches_the_program_lax_path(builder):
    graph = models.MODELS[builder](**TINY[builder])
    params = system.make_params(graph, seed=2**35 + 3)
    images = system.make_images(graph, seed=2**35 + 3, n=3)
    ref = reference.logits(graph, params, images, block=2)
    assert ref.shape == (3, 10)
    assert norm_err(program_lax(graph, params, images), ref) < 1e-5
    low = reference.logits(graph, params, images, dtype=jax.numpy.bfloat16)
    assert norm_err(low, ref) > 1e-3


def test_weights_and_images_follow_the_seed():
    graph = models.googlenet(**TINY["googlenet"])
    a = system.make_params(graph, 2**40)
    b = system.make_params(graph, 2**40)
    c = system.make_params(graph, 2**40 + 2**31)
    conv1 = min(a)
    np.testing.assert_array_equal(a[conv1]["w"], b[conv1]["w"])
    assert not np.array_equal(a[conv1]["w"], c[conv1]["w"])
    assert float(np.abs(a[conv1]["b"]).min()) > 0
    np.testing.assert_array_equal(system.make_images(graph, 5, 2),
                                  system.make_images(graph, 5, 2))
    with pytest.raises(ValueError):
        system.prng_key(-1)
