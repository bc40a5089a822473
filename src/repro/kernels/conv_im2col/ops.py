"""Public wrapper for the implicit-GEMM im2col convolution.

The induced GEMM is (O1·O2, K1K2·Cin) × (K1K2·Cin, Cout); the plan's
dataflow binds (p1, p2) onto two of those dims (Eq. 9) and this wrapper
translates that binding into the kernel's (output-row, C_out) tiling:
the M-dim block covers ~bm GEMM rows (bo1 = bm // O2 output rows), the
N-dim block is bn. The K dim streams in Cin chunks of at most 128 lanes,
each chunk's window band gathered in VMEM. Accepts (H, W, Cin) or batched
(B, H, W, Cin) inputs.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.cost_model import Dataflow
from repro.kernels.common import (batchable, ceil_to, default_interpret,
                                  pad_bias, phase_split, sublanes)
from repro.kernels.conv_im2col.conv_im2col import conv_im2col_call
from repro.kernels.gemm.ops import dataflow_blocks, toeplitz_gemm
from repro.kernels.layouts import materialize, restore


@batchable
@functools.partial(jax.jit, static_argnames=(
    "stride", "padding", "dataflow", "p1", "p2", "interpret", "epilogue",
    "in_layout", "out_layout", "out_scale"))
def conv_im2col(x: jax.Array, w: jax.Array, stride: int = 1,
                padding: str = "SAME",
                dataflow: Dataflow = Dataflow.NS,
                p1: int = 128, p2: int = 128,
                interpret: Optional[bool] = None,
                epilogue: str = "none",
                bias: Optional[jax.Array] = None,
                in_layout=None, out_layout=None,
                scale: Optional[jax.Array] = None,
                out_scale: Optional[float] = None) -> jax.Array:
    """Convolution via the im2col algorithm. x: (H, W, Cin) or (B, H, W, Cin),
    w: (K1, K2, Cin, Cout) → (…, O1, O2, Cout). ``epilogue`` fuses the
    post-GEMM auxiliary unit (ReLU / bias) into the kernel's output flush.

    ``in_layout``/``out_layout`` (``core.layouts.LayoutSpec``) realize the
    plan's store formats: a "toeplitz" ``in_layout`` means ``x`` IS the
    layer's Toeplitz matrix — the window gather was paid once at the
    producer's store, so the layer is a plain dataflow-bound GEMM; a
    non-NHWC ``out_layout`` emits the consumer's store format directly.

    Int8 path: ``x``/``w`` already quantized (overlay does it), ``scale``
    is the per-output-channel dequant vector and ``out_scale`` (static)
    requantizes the fused epilogue's result to an int8 output."""
    interpret = default_interpret() if interpret is None else interpret
    if in_layout is not None and in_layout.kind == "toeplitz":
        out = toeplitz_gemm(x, w.reshape(-1, w.shape[-1]), in_layout,
                            dataflow, p1, p2, interpret=interpret,
                            epilogue=epilogue, bias=bias, scale=scale,
                            out_scale=out_scale)
        return materialize(out, out_layout)
    x = restore(x, in_layout)
    h, w_dim, c_in = x.shape
    k1, k2, _, c_out = w.shape
    if padding == "SAME":
        o1, o2 = -(-h // stride), -(-w_dim // stride)
        pt = max((o1 - 1) * stride + k1 - h, 0) // 2
        pl_ = max((o2 - 1) * stride + k2 - w_dim, 0) // 2
    else:
        o1 = (h - k1) // stride + 1
        o2 = (w_dim - k2) // stride + 1
        pt = pl_ = 0
    bm, bn, _ = dataflow_blocks(dataflow, p1, p2)
    # Output width padded to whole sublane tiles, so an output-row block
    # flattens to GEMM rows without a relayout; rows padded to whole
    # blocks. Both paddings compute rows that are sliced off afterwards.
    o2p = ceil_to(o2, sublanes(x.dtype))
    bo1 = min(max(1, bm // o2p), o1)
    o1p = ceil_to(o1, bo1)
    # Cin streams in chunks of at most 128 lanes (the K panel).
    cc = ceil_to(c_in, sublanes(x.dtype)) if c_in <= 128 else 128
    c_inp = ceil_to(c_in, cc)
    # Rows/cols every window needs, rounded up to whole stride phases.
    need_r = (o1p + (k1 - 1) // stride) * stride
    need_c = (o2p + (k2 - 1) // stride) * stride
    xp = jnp.pad(x, ((pt, max(0, need_r - h - pt)),
                     (pl_, max(0, need_c - w_dim - pl_)),
                     (0, c_inp - c_in)))[:need_r, :need_c]
    bc = min(bn, ceil_to(c_out, 128))
    c_outp = ceil_to(c_out, bc)
    wm = jnp.pad(w.reshape(k1 * k2, c_in, c_out),
                 ((0, 0), (0, c_inp - c_in), (0, c_outp - c_out)))
    out = conv_im2col_call(phase_split(xp, stride), wm, k1=k1, k2=k2,
                           stride=stride, o1=o1p, o2=o2p, bo1=bo1, bc=bc, cc=cc,
                           interpret=interpret, epilogue=epilogue,
                           bias=pad_bias(bias, c_out, c_outp),
                           scale=pad_bias(scale, c_out, c_outp),
                           out_scale=out_scale)
    return materialize(out[:o1, :o2, :c_out], out_layout)
