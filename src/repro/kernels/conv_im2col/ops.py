"""Public wrapper for the implicit-GEMM im2col convolution.

The induced GEMM is (O1·O2, K1K2·Cin) × (K1K2·Cin, Cout); the plan's
dataflow binds (p1, p2) onto two of those dims (Eq. 9) and this wrapper
translates that binding into the kernel's (output-row, C_out) tiling:
the M-dim block covers ~bm GEMM rows (bo1 = bm // O2 output rows), the
N-dim block is bn. The K dim streams in Cin chunks of at most 128 lanes,
each chunk's window band gathered in VMEM. Accepts (H, W, Cin) or batched
(B, H, W, Cin) inputs.

A conv with a narrow input and few taps — the network's stem — takes the
dense route instead (``dense_stem``): its stride folds into channels and
its whole contraction becomes one lane-dense K panel, where Cin chunks of
a few lanes would waste most of every tile, DMA and store.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.cost_model import Dataflow
from repro.kernels.common import (batchable, ceil_to, default_interpret,
                                  pad_bias, phase_split, sublanes)
from repro.kernels.conv_im2col.conv_im2col import (conv_im2col_call,
                                                   conv_stem_call,
                                                   stem_fits_vmem)
from repro.kernels.gemm.ops import dataflow_blocks, toeplitz_gemm
from repro.kernels.layouts import materialize, restore

# The dense stem's contraction fits one K panel of this many lanes, and
# one grid step covers about this many GEMM rows (output positions).
_STEM_K = 256
_STEM_M = 2048


def _stem_k(c_in: int, k1: int, k2: int, stride: int) -> int:
    """The dense stem's K: its folded taps × Cin·s² channels padded to
    whole 8-row tiles."""
    kq1, kq2 = -(-k1 // stride), -(-k2 // stride)
    return kq1 * kq2 * ceil_to(c_in * stride * stride, 8)


def _out_shape(h: int, w_dim: int, k1: int, k2: int, stride: int,
               padding: str):
    """(O1, O2, top pad, left pad) of a conv, TF-style SAME or VALID."""
    if padding == "SAME":
        o1, o2 = -(-h // stride), -(-w_dim // stride)
        pt = max((o1 - 1) * stride + k1 - h, 0) // 2
        pl_ = max((o2 - 1) * stride + k2 - w_dim, 0) // 2
        return o1, o2, pt, pl_
    return (h - k1) // stride + 1, (w_dim - k2) // stride + 1, 0, 0


def _stem_shapes(o1: int, o2: int, k1: int, k2: int, stride: int):
    """The dense stem's schedule: its folded taps (kq1, kq2), output rows
    a grid step, output rows and columns padded (o1p, o2s), and the padded
    input's rows and columns (hp, wp)."""
    s = stride
    kq1, kq2 = -(-k1 // s), -(-k2 // s)
    o2s = ceil_to(o2, 8)
    o2p = ceil_to(o2s, 128)         # output columns ride the lanes
    rows = min(o1, max(1, _STEM_M // o2p))
    rows = -(-o1 // -(-o1 // rows))             # even blocks of rows
    o1p = ceil_to(o1, rows)
    hp = ceil_to(o1p + kq1 - 1, 128) * s
    wp = ceil_to(o2p + kq2 - 1, 128) * s
    return kq1, kq2, rows, o1p, o2s, hp, wp


def dense_stem(x_shape, w_shape, stride: int, padding: str, dtype,
               in_layout=None) -> bool:
    """Whether an im2col conv of one (H, W, Cin) image by (K1, K2, Cin,
    Cout) weights takes the dense stem route: an NHWC float32 input (the
    fold reads with sublane strides, which TPU loads offer for 32-bit data
    alone), more than one tap, a per-tap chunk narrower than a lane tile
    once the stride folds into channels (Cin·s² < 128), a whole folded
    contraction within one ``_STEM_K``-lane panel, and an image small
    enough for the kernel to hold in VMEM."""
    h, w_dim, c_in = x_shape
    k1, k2, _, c_out = w_shape
    if not ((in_layout is None or in_layout.kind == "nhwc")
            and jnp.dtype(dtype) == jnp.float32
            and k1 * k2 > 1 and c_in * stride * stride < 128
            and _stem_k(c_in, k1, k2, stride) <= _STEM_K):
        return False
    o1, o2, _, _ = _out_shape(h, w_dim, k1, k2, stride, padding)
    kq1, kq2, rows, _, o2s, hp, wp = _stem_shapes(o1, o2, k1, k2, stride)
    return stem_fits_vmem(c_in, hp, wp, ceil_to(c_out, 128),
                          _stem_k(c_in, k1, k2, stride), jnp.float32,
                          stride=stride, taps=kq1 * kq2, rows=rows, o2=o2s)


def _conv_stem(x, w, stride, o1, o2, pt, pl_, interpret, epilogue, bias):
    """The dense stem route. Fold the stride's s×s phases into channels:
    a (K1, K2) stride-s conv becomes a (⌈K1/s⌉, ⌈K2/s⌉) stride-1 conv
    over Cin·s² channels, its weights re-packed with zero taps where the
    window does not reach. The input goes to the kernel channel-major and
    padded, a layout XLA makes without moving a lane (an NHWC image with
    few channels is stored channel-major already); the kernel folds it in
    VMEM and packs all taps into one dense K panel."""
    h, w_dim, c_in = x.shape
    k1, k2, _, c_out = w.shape
    s = stride
    kq1, kq2, rows, o1p, o2s, hp, wp = _stem_shapes(o1, o2, k1, k2, s)
    xc = jnp.pad(jnp.transpose(x, (2, 0, 1)),
                 ((0, 0), (pt, max(0, hp - h - pt)),
                  (pl_, max(0, wp - w_dim - pl_))))[:, :hp, :wp]
    # Weights in (row tap, column tap, ph, pw, c) order.
    cf = s * s * c_in
    cs = ceil_to(cf, 8)
    wq = jnp.pad(w, ((0, kq1 * s - k1), (0, kq2 * s - k2), (0, 0), (0, 0)))
    wq = wq.reshape(kq1, s, kq2, s, c_in, c_out).transpose(0, 2, 1, 3, 4, 5)
    c_outp = ceil_to(c_out, 128)
    wq = jnp.pad(wq.reshape(kq1 * kq2, cf, c_out),
                 ((0, 0), (0, cs - cf), (0, c_outp - c_out)))
    out = conv_stem_call(xc, wq.reshape(-1, c_outp).T, stride=s, kq1=kq1,
                         kq2=kq2, o1=o1p, o2=o2s, rows=rows,
                         interpret=interpret, epilogue=epilogue,
                         bias=pad_bias(bias, c_out, c_outp))
    return out[:o1, :o2, :c_out]


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
    o1, o2, pt, pl_ = _out_shape(h, w_dim, k1, k2, stride, padding)
    if dense_stem(x.shape, w.shape, stride, padding, x.dtype, in_layout):
        # The stem's schedule follows its shape alone: no binding of the
        # plan makes a single dense K panel any denser.
        return materialize(_conv_stem(x, w, stride, o1, o2, pt, pl_,
                                      interpret, epilogue, bias), out_layout)
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
