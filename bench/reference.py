"""The plain reference: a straightforward walk of the CNN graph.

It reads only the ``Graph`` (the specification: layer kinds, ``ConvMeta``
shapes, pooling windows) and imports nothing of the program's executor,
overlay, kernels or mapper. Every conv is ``lax.conv_general_dilated``
followed by bias and ReLU (the graph's CONV -> ReLU semantics), pools are
``reduce_window``, concat joins channels, the global pool is a mean and
the classifier a dense layer.

``logits`` runs it in float32 in blocks of images, at the matmul
precision the configuration states. The controls, one step below that
precision, are the same walk in ``int8`` (each conv and dense layer's
input quantized per image and its weights per output channel, symmetric,
accumulated in int32) and in ``bfloat16`` (weights, inputs and every
intermediate array in bfloat16). ``f32_logits`` keeps the bfloat16 walk
but has the classifier write float32 logits: bfloat16 storage of every
activation but the output.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def _pad(p: str) -> str:
    return "SAME" if p == "same" else "VALID"


def _windows(k: int, s: int):
    return (1, k, k, 1), (1, s, s, 1)


def _quantize(a: jax.Array, axes) -> tuple:
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis=axes, keepdims=True),
                        1e-12) / 127.0
    return jnp.clip(jnp.round(a / scale), -127, 127).astype(jnp.int8), scale


def _conv(x, w, stride, pad, int8):
    dims = ("NHWC", "HWIO", "NHWC")
    if not int8:
        return jax.lax.conv_general_dilated(x, w, (stride, stride), pad,
                                            dimension_numbers=dims)
    xq, sx = _quantize(x, (1, 2, 3))
    wq, sw = _quantize(w, (0, 1, 2))
    y = jax.lax.conv_general_dilated(xq, wq, (stride, stride), pad,
                                     dimension_numbers=dims,
                                     preferred_element_type=jnp.int32)
    return y.astype(jnp.float32) * sx * sw.reshape(-1)


def _dense(x, w, int8, f32_logits=False):
    if f32_logits:
        return jnp.dot(x, w, preferred_element_type=jnp.float32)
    if not int8:
        return x @ w
    xq, sx = _quantize(x, (1,))
    wq, sw = _quantize(w, (0,))
    y = jax.lax.dot(xq, wq, preferred_element_type=jnp.int32)
    return y.astype(jnp.float32) * sx * sw


def forward(graph, params: Dict[int, Dict[str, jax.Array]],
            x: jax.Array, int8: bool = False, f32_logits: bool = False
            ) -> jax.Array:
    """Logits of a batch ``x`` (B, H, W, C); the dtype of ``x`` and
    ``params`` is the dtype of every intermediate, ``int8`` quantizes
    the input of every conv and dense layer, and ``f32_logits`` has the
    classifier write float32 whatever its inputs."""
    vals: Dict[int, jax.Array] = {}
    for nid in graph.topo_order():
        node = graph.nodes[nid]
        kind = node.kind.value
        ins = [vals[p] for p in graph.predecessors(nid)]
        if kind == "input":
            y = x
        elif kind == "conv":
            m = node.conv
            y = _conv(ins[0], params[nid]["w"], m.stride, _pad(m.pad), int8)
            y = jnp.maximum(y + params[nid]["b"], 0)
        elif kind == "pool_max":
            win, st = _windows(int(node.attrs["k"]), int(node.attrs["stride"]))
            y = jax.lax.reduce_window(ins[0], -jnp.inf, jax.lax.max, win, st,
                                      _pad(node.attrs.get("pad", "same")))
        elif kind == "pool_avg":
            win, st = _windows(int(node.attrs["k"]), int(node.attrs["stride"]))
            pad = _pad(node.attrs.get("pad", "same"))
            total = jax.lax.reduce_window(ins[0], 0.0, jax.lax.add, win, st,
                                          pad)
            count = jax.lax.reduce_window(jnp.ones_like(ins[0]), 0.0,
                                          jax.lax.add, win, st, pad)
            y = total / count
        elif kind == "concat":
            y = jnp.concatenate(ins, axis=-1)
        elif kind == "add":
            y = jnp.maximum(sum(ins), 0)
        elif kind == "global_pool":
            y = jnp.mean(ins[0], axis=(1, 2), keepdims=True)
        elif kind == "fc":
            flat = ins[0].reshape(ins[0].shape[0], -1)
            b = params[nid]["b"]
            y = (_dense(flat, params[nid]["w"], int8, f32_logits)
                 + (b.astype(jnp.float32) if f32_logits else b))
        elif kind == "softmax":
            y = jax.nn.softmax(ins[0], axis=-1)
        elif kind == "output":
            y = ins[0]
        else:
            raise ValueError(f"reference: unknown layer kind {kind!r}")
        vals[nid] = y
    out = vals[graph.sink()]
    return out.reshape(out.shape[0], -1)


def logits(graph, params, images: np.ndarray, precision: str = "highest",
           block: int = 16, dtype=jnp.float32, int8: bool = False,
           f32_logits: bool = False) -> np.ndarray:
    """Reference logits (N, classes) as float32 numpy, ``block`` images at
    a time so that a large network fits beside what the process holds.
    ``precision`` is JAX's matmul precision for the float walk."""
    cast = jax.tree.map(lambda a: jnp.asarray(a, dtype), params)
    fn = jax.jit(lambda p, xb: forward(graph, p, xb, int8, f32_logits))
    out = []
    with jax.default_matmul_precision(precision):
        for i in range(0, len(images), block):
            xb = jnp.asarray(images[i:i + block], dtype)
            out.append(np.asarray(fn(cast, xb), np.float32))
    return np.concatenate(out)
