"""The system under test, built the way a user builds it.

The graph comes from the program's model builders, the plan from its own
``identify_parameters`` + ``map_network``, and the server is
``CNNServingEngine(use_pallas=True, epilogue="bias_relu", warmup=True)``
with every other option at the program's default, except the bucket
ladder that the traffic file names. Weights and images are the
benchmark's own, made from the seed.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts


def build_graph(config: Dict):
    from repro.cnn.models import MODELS
    graph = MODELS[config["builder"]](**config["args"])
    if "expect" in config:
        counts.check_totals(graph, config["expect"])
    return graph


def input_shape(graph) -> tuple:
    return tuple(int(d) for d in graph.nodes[graph.source()]
                 .attrs["out_shape"])


def prng_key(seed: int) -> jax.Array:
    """A key for any non-negative seed, 64 bits or more included."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make_params(graph, seed: int) -> Dict[int, Dict[str, jax.Array]]:
    """Weights in the executor's layout ``{nid: {"w", "b"}}``, made on the
    device from the seed in one jitted call: He-normal conv weights (HWIO),
    1/sqrt(fan_in) dense weights, and non-zero biases so that the fused
    bias epilogue does real work."""
    shapes = {}
    for nid in graph.topo_order():
        node = graph.nodes[nid]
        if node.kind.value == "conv":
            m = node.conv
            shapes[nid] = ((m.k1, m.k2, m.c_in, m.c_out), m.c_out,
                           math.sqrt(2.0 / (m.k1 * m.k2 * m.c_in)))
        elif node.kind.value == "fc":
            fin = int(node.attrs["in_features"])
            fout = int(node.attrs["out_features"])
            shapes[nid] = ((fin, fout), fout, math.sqrt(1.0 / fin))

    def init(key):
        # One draw for every weight and bias, sliced at static offsets:
        # one random op keeps the program small and quick to compile.
        total = sum(math.prod(w) + nb for w, nb, _ in shapes.values())
        flat = jax.random.normal(key, (total,), jnp.float32)
        out, at = {}, 0
        for nid, (wshape, nb, std) in shapes.items():
            n = math.prod(wshape)
            out[nid] = {"w": std * flat[at:at + n].reshape(wshape),
                        "b": 0.05 * flat[at + n:at + n + nb]}
            at += n + nb
        return out

    return jax.jit(init)(prng_key(seed))


def make_images(graph, seed: int, n: int) -> np.ndarray:
    """The pool of ``n`` distinct images the traffic cycles through."""
    rng = np.random.default_rng([seed, 1])
    return rng.standard_normal((n,) + input_shape(graph), np.float32)


def plan(graph):
    from repro.core.dse import identify_parameters
    from repro.core.mapper import map_network
    return map_network(graph, hw=identify_parameters(graph))


def make_engine(graph, params, the_plan, engine_opts: Dict, chips: int):
    """The server. With ``chips`` > 1 the batch is sharded over a data mesh
    of that many devices, as ``examples/serve_cnn.py`` serves."""
    from repro.serving.cnn_engine import CNNServingEngine
    mesh = None
    if chips > 1:
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(chips)
    return CNNServingEngine(graph, params, the_plan, use_pallas=True,
                            epilogue="bias_relu", warmup=True, mesh=mesh,
                            **engine_opts)
