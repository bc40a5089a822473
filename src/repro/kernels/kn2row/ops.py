"""Public wrapper: full kn2row convolution = batched unit-conv GEMMs
(Pallas) + pad-and-accumulate (Pallas).

The unit-conv GEMM is (H·W, Cin) × (Cin, Cout); the plan's dataflow binds
(p1, p2) straight onto the (bm, bn, bk) block dims via Eq. 9 — kn2row is the
one algorithm whose GEMM shape matches the binding with no translation.
Accepts (H, W, Cin) or batched (B, H, W, Cin) inputs.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.cost_model import Dataflow
from repro.kernels.common import (batchable, ceil_to, default_interpret,
                                  pad_bias, phase_split, sublanes)
from repro.kernels.gemm.ops import dataflow_blocks
from repro.kernels.kn2row.kn2row import pad_accumulate, unit_conv_gemms
from repro.kernels.layouts import materialize, restore


@batchable
@functools.partial(jax.jit, static_argnames=(
    "stride", "padding", "dataflow", "p1", "p2", "interpret", "epilogue",
    "in_layout", "out_layout", "out_scale"))
def conv_kn2row(x: jax.Array, w: jax.Array, stride: int = 1,
                padding: str = "SAME",
                dataflow: Dataflow = Dataflow.NS,
                p1: int = 128, p2: int = 128,
                interpret: Optional[bool] = None,
                epilogue: str = "none",
                bias: Optional[jax.Array] = None,
                in_layout=None, out_layout=None,
                scale: Optional[jax.Array] = None,
                out_scale: Optional[float] = None) -> jax.Array:
    """Convolution via kn2row. x: (H, W, Cin) or (B, H, W, Cin),
    w: (K1, K2, Cin, Cout) → (…, O1, O2, Cout). ``epilogue`` fuses the
    post-GEMM auxiliary unit into the final pad-accumulate flush.

    kn2row's input layout IS the 3-D tensor (§3.3), so a matched
    ``in_layout`` is simply NHWC; other layouts are restored on entry
    (converting load), and ``out_layout`` emits a consumer's store format."""
    interpret = default_interpret() if interpret is None else interpret
    x = restore(x, in_layout)
    h, w_dim, c_in = x.shape
    k1, k2, _, c_out = w.shape
    if padding == "SAME":
        o1, o2 = -(-h // stride), -(-w_dim // stride)
        ph = max((o1 - 1) * stride + k1 - h, 0)
        pw = max((o2 - 1) * stride + k2 - w_dim, 0)
        pt, pl_ = ph // 2, pw // 2
    else:
        o1 = (h - k1) // stride + 1
        o2 = (w_dim - k2) // stride + 1
        pt = pl_ = 0

    bm, bn, bk = dataflow_blocks(dataflow, p1, p2)
    # Pad the map once for every shifted window (SAME halo, whole output
    # row blocks of ~bm positions, whole stride phases) and phase-split it
    # by the stride, so phase 1 emits patches that phase 2 reads with
    # unit-stride windows. Zero rows stay zero through the bias-free unit
    # convs.
    o2p = ceil_to(o2, sublanes(x.dtype))
    bo1 = min(max(1, bm // o2p), o1)
    o1p = ceil_to(o1, bo1)
    need_r = (o1p + (k1 - 1) // stride) * stride
    need_c = (o2p + (k2 - 1) // stride) * stride
    xp = jnp.pad(x, ((pt, max(0, need_r - h - pt)),
                     (pl_, max(0, need_c - w_dim - pl_)),
                     (0, 0)))[:need_r, :need_c]
    xph = phase_split(xp, stride)                 # (S·S, Hq, Wq, Cin)

    # Phase 1: (S·S·Hq·Wq, Cin) @ (K1K2, Cin, Cout) under the plan's block
    # binding.
    m = xph.size // c_in
    # int8 blocks need the (32, 128) minimum tile on real hardware.
    bm_ = min(bm, ceil_to(m, sublanes(x.dtype)))
    bn_ = min(bn, ceil_to(c_out, 128))
    bk_ = min(bk, ceil_to(c_in, 128))
    mp, np_, kp = ceil_to(m, bm_), ceil_to(c_out, bn_), ceil_to(c_in, bk_)
    x2d = jnp.pad(xph.reshape(m, c_in), ((0, mp - m), (0, kp - c_in)))
    wk = jnp.pad(w.reshape(k1 * k2, c_in, c_out),
                 ((0, 0), (0, kp - c_in), (0, np_ - c_out)))
    p = unit_conv_gemms(x2d, wk, bm=bm_, bn=bn_, bk=bk_,
                        interpret=interpret)          # (K1K2, mp, np_)
    p = p[:, :m, :].reshape(k1, k2, *xph.shape[:3], np_)

    # Phase 2: accumulate the shifted windows on-chip.
    out = pad_accumulate(p, k1=k1, k2=k2, o1=o1p, o2=o2p, bo1=bo1,
                         stride=stride, interpret=interpret,
                         epilogue=epilogue,
                         bias=pad_bias(bias, c_out, np_),
                         scale=pad_bias(scale, c_out, np_),
                         out_scale=out_scale)
    return materialize(out[:o1, :o2, :c_out], out_layout)
