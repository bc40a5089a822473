"""Shared helpers for the Pallas kernels."""
from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from repro.core.cost_model import V5E


# Post-GEMM epilogues the Computing Unit can fuse into a kernel's output
# flush (§3's in-pipeline auxiliary units: the conv output streams through
# ReLU/bias without a DRAM round trip). "none" is the identity.
EPILOGUES = ("none", "relu", "bias", "bias_relu")

# Symmetric int8: zero-point 0, range [-127, 127] (−128 excluded so the
# range is sign-symmetric and |q|·|q| accumulation bounds stay tight).
INT8_MAX = 127
_SCALE_EPS = 1e-12

# Per-layer precisions the mapper can assign. Winograd is bf16-only (its
# input/output transforms amplify quantization error), which the cost
# graph encodes by never emitting an int8 label for Winograd algorithms.
PRECISIONS = ("bf16", "int8")


def apply_epilogue(y: jax.Array, epilogue: str,
                   bias: jax.Array = None, *,
                   scale: jax.Array = None,
                   out_scale: float = None) -> jax.Array:
    """Apply a named epilogue; ``bias`` broadcasts over the minor dim.

    Quantized variants: ``scale`` (broadcasting over the minor dim, the
    per-output-channel ``in_scale * w_scale`` product) dequantizes an
    int32 accumulator to f32 *before* bias/relu; ``out_scale`` (a static
    per-tensor float) requantizes the epilogue result back to int8
    *after* bias/relu — so CONV+bias+ReLU+requant is one fused flush.
    """
    if epilogue not in EPILOGUES:
        raise ValueError(f"unknown epilogue {epilogue!r}; want {EPILOGUES}")
    if scale is not None:
        y = y.astype(jnp.float32) * scale
    if epilogue.startswith("bias"):
        if bias is None:
            raise ValueError(f"epilogue {epilogue!r} needs a bias array")
        y = y + bias.astype(y.dtype)
    if epilogue.endswith("relu"):
        y = jnp.maximum(y, 0)
    if out_scale is not None:
        y = requantize(y, out_scale)
    return y


def quantize(x: jax.Array, scale) -> jax.Array:
    """f32 → symmetric int8 with the given scale (array or python float).

    ``scale`` broadcasts, so a per-tensor scalar and a per-output-channel
    vector both work; values round to nearest and saturate at ±INT8_MAX.
    """
    q = jnp.round(x.astype(jnp.float32) / scale)
    return jnp.clip(q, -INT8_MAX, INT8_MAX).astype(jnp.int8)


def dequantize(q: jax.Array, scale) -> jax.Array:
    """int8 (or int32 accumulator) → f32: multiply by the scale."""
    return q.astype(jnp.float32) * scale


def requantize(y: jax.Array, out_scale: float) -> jax.Array:
    """f32 epilogue output → int8 at the consumer's activation scale."""
    q = jnp.round(y.astype(jnp.float32) / out_scale)
    return jnp.clip(q, -INT8_MAX, INT8_MAX).astype(jnp.int8)


def weight_scales(w: jax.Array) -> jax.Array:
    """Per-output-channel symmetric scales for a weight tensor whose LAST
    axis is the output channel (both (K1,K2,Cin,Cout) and (K,Cout) 2-D
    GEMM operands qualify). Returns an f32 vector of shape (Cout,)."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)),
                   axis=tuple(range(w.ndim - 1)))
    return jnp.maximum(amax, _SCALE_EPS) / INT8_MAX


def pad_bias(bias, n: int, n_padded: int):
    """Prep a fused-epilogue bias for a Pallas kernel: (N,) → (1, N_padded),
    zero-padded channels (they are sliced away with the padded output)."""
    if bias is None:
        return None
    assert bias.shape == (n,), (bias.shape, n)
    return jnp.pad(bias, (0, n_padded - n)).reshape(1, n_padded)


def ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def pad_to(x: jax.Array, multiples: Sequence[int]) -> jax.Array:
    """Zero-pad each dim of ``x`` up to the given multiple (0 = leave alone)."""
    pads = []
    for dim, mult in zip(x.shape, multiples):
        pads.append((0, (ceil_to(dim, mult) - dim) if mult else 0))
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def batchable(fn):
    """Lift a single-image conv ``fn(x, ...)`` to also accept a batched
    input by vmapping over the leading axis.

    The un-batched rank of ``x`` depends on the input layout the call
    carries (``in_layout`` kwarg, a ``core.layouts.LayoutSpec``): NHWC is
    rank 3, a Toeplitz matrix rank 2, Winograd tiles rank 4 — one extra
    dim means a batch. Pallas kernels batch via ``pallas_call``'s batching
    rule (an extra outer grid dimension), so one compiled program serves
    the whole batch; the jnp reference paths batch for free.
    """
    @functools.wraps(fn)
    def wrapper(x, *args, **kwargs):
        spec = kwargs.get("in_layout")
        base = 3 if spec is None or spec.kind == "nhwc" else spec.base_rank
        if x.ndim == base + 1:
            return jax.vmap(lambda xi: fn(xi, *args, **kwargs))(x)
        return fn(x, *args, **kwargs)
    return wrapper


def default_interpret() -> bool:
    """Kernels run in interpret mode exactly where no TPU backend exists."""
    return jax.default_backend() != "tpu"


def phase_split(x: jax.Array, s: int) -> jax.Array:
    """(H, W, C), H and W multiples of ``s`` → (s·s, H/s, W/s, C), where
    phase ``a·s + b`` holds ``x[a::s, b::s]``. A stride-s window read
    becomes a unit-stride read of one phase: kernels never issue strided
    loads, which Mosaic refuses for 8- and 16-bit data."""
    if s == 1:
        return x[None]
    h, w, c = x.shape
    x = x.reshape(h // s, s, w // s, s, c).transpose(1, 3, 0, 2, 4)
    return x.reshape(s * s, h // s, w // s, c)


def sublanes(dtype) -> int:
    """Rows of one native VMEM tile: 8 for 32-bit, 16 for 16-bit and 32
    for 8-bit elements (all 128 lanes wide)."""
    return 32 // np.dtype(dtype).itemsize


def vmem_bytes(shape: Sequence[int], dtype) -> int:
    """VMEM bytes one buffer of ``shape`` occupies once its last two dims
    are padded to the native (sublanes, 128) tile."""
    *lead, rows, lanes = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    return (math.prod(lead) * ceil_to(rows, sublanes(dtype))
            * ceil_to(lanes, 128) * np.dtype(dtype).itemsize)


def compiler_params(blocks, scratch=()) -> pltpu.CompilerParams:
    """Mosaic parameters whose scoped-VMEM limit fits the kernel.

    ``blocks`` are the ``(shape, dtype)`` of every pipelined input and
    output block, which Pallas double-buffers; ``scratch`` are the
    ``(shape, dtype)`` of single-buffered scratch. Half as much again is
    left for Mosaic's own temporaries (a dot's result, a transform's
    partial sums); the limit never exceeds the chip's VMEM.
    """
    footprint = (2 * sum(vmem_bytes(s, d) for s, d in blocks)
                 + sum(vmem_bytes(s, d) for s, d in scratch))
    limit = footprint + footprint // 2 + (4 << 20)
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(min(limit, V5E.vmem_bytes)))
