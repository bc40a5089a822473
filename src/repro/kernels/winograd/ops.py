"""Public Winograd conv: transforms (Pallas) + batched GEMM (Pallas),
with the multi-round decomposition for kernels larger than r×r.

The transform-space Hadamard products are (tiles, Cin) × (Cin, Cout) GEMMs
batched over the (m+r-1)² tile positions; the plan's dataflow/(p1, p2)
binding is forwarded to that batched GEMM's block dims (Eq. 9).
Accepts (H, W, Cin) or batched (B, H, W, Cin) inputs.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.cost_model import Dataflow
from repro.kernels.common import (apply_epilogue, batchable, ceil_to,
                                  default_interpret, pad_bias)
from repro.kernels.gemm.ops import batched_gemm
from repro.kernels.layouts import materialize, restore
from repro.kernels.winograd.winograd import (input_transform,
                                             input_transform_tiles,
                                             output_transform,
                                             transform_kernel_weights)


def _lanes(c: int) -> int:
    """Channel count the transform kernels take: at most 128, or whole
    128-lane blocks."""
    return c if c <= 128 else ceil_to(c, 128)


def _winograd_gemm_out(v: jax.Array, w: jax.Array, m: int,
                       dataflow: Dataflow, p1: int, p2: int,
                       interpret: bool, epilogue: str,
                       bias: Optional[jax.Array]) -> jax.Array:
    """Shared tail of the pipeline: V (T², tiles_y, txp, Cin_p) → batched
    transform-space GEMM → output transform with the fused epilogue →
    spatial (tiles_y·m, txp·m, Cout_p)."""
    r = w.shape[0]
    tt, ty, txp, c_inp = v.shape
    c_in, c_out = w.shape[2:]
    c_outp = _lanes(c_out)
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, c_inp - c_in), (0, c_outp - c_out)))
    u = transform_kernel_weights(wp, m, r).astype(v.dtype)  # (T², Cin, Cout)
    mm = batched_gemm(v.reshape(tt, ty * txp, c_inp), u, dataflow=dataflow,
                      p1=p1, p2=p2, interpret=interpret,
                      out_dtype=v.dtype)              # (T², n_tiles, Cout)
    return output_transform(mm.reshape(tt, ty, txp, c_outp), m=m, r=r,
                            interpret=interpret, epilogue=epilogue,
                            bias=pad_bias(bias, c_out, c_outp))


def _conv_f_mr(x: jax.Array, w: jax.Array, m: int, o1: int, o2: int,
               pt: int, pl_: int, dataflow: Dataflow, p1: int, p2: int,
               interpret: bool, epilogue: str = "none",
               bias: Optional[jax.Array] = None) -> jax.Array:
    """Single-round F(m,r) same-stride-1 conv core; x unpadded (H, W, Cin).
    The epilogue fuses into the output transform — the last kernel of the
    Winograd pipeline."""
    r = w.shape[0]
    h, w_dim, c_in = x.shape
    c_out = w.shape[-1]
    ty, tx = -(-o1 // m), -(-o2 // m)
    need_r, need_c = ty * m + r - 1, tx * m + r - 1
    xp = jnp.pad(x, ((pt, max(0, need_r - h - pt)),
                     (pl_, max(0, need_c - w_dim - pl_)),
                     (0, _lanes(c_in) - c_in)))
    v = input_transform(xp, m=m, r=r, tiles_y=ty, tiles_x=tx,
                        interpret=interpret)          # (T², ty, txp, Cin)
    y = _winograd_gemm_out(v, w, m, dataflow, p1, p2, interpret, epilogue,
                           bias)
    return y[:o1, :o2, :c_out]


def _conv_from_tiles(tiles: jax.Array, w: jax.Array, m: int, spec,
                     dataflow: Dataflow, p1: int, p2: int,
                     interpret: bool, epilogue: str,
                     bias: Optional[jax.Array]) -> jax.Array:
    """Matched scattered-layout consumer (§3.3): the producer stored this
    layer's (T, T) input tiles, so the spatial re-gather is skipped and the
    pipeline is tile transform → batched GEMM → output transform."""
    r = w.shape[0]
    c_in, c_out = w.shape[2:]
    tiles = jnp.pad(tiles, ((0, 0),) * 3 + ((0, _lanes(c_in) - c_in),))
    v = input_transform_tiles(tiles, m=m, r=r, tiles_y=spec.tiles_y,
                              tiles_x=spec.tiles_x, interpret=interpret)
    y = _winograd_gemm_out(v, w, m, dataflow, p1, p2, interpret, epilogue,
                           bias)
    return y[:spec.o1, :spec.o2, :c_out]


@batchable
@functools.partial(jax.jit, static_argnames=(
    "m", "padding", "dataflow", "p1", "p2", "interpret", "epilogue",
    "in_layout", "out_layout"))
def conv_winograd(x: jax.Array, w: jax.Array, m: int = 2,
                  padding: str = "SAME",
                  dataflow: Dataflow = Dataflow.NS,
                  p1: int = 128, p2: int = 128,
                  interpret: Optional[bool] = None,
                  epilogue: str = "none",
                  bias: Optional[jax.Array] = None,
                  in_layout=None, out_layout=None) -> jax.Array:
    """Winograd convolution, stride 1, square K×K kernels.

    K > r runs in ceil(K/r)² rounds of shifted r×r sub-kernels with output
    accumulation (§6.1.2's K1K2/r² rounds). Single-round kernels fuse the
    epilogue into the output transform; the multi-round path must apply it
    after the cross-round accumulation (ReLU does not distribute over +).

    A matching "winograd" ``in_layout`` (same m, single-round K == r) means
    ``x`` is already the scattered tile layout — the layer consumes it
    without the spatial re-gather; any other layout is restored on entry.
    A non-NHWC ``out_layout`` emits the consumer's store format.
    """
    interpret = default_interpret() if interpret is None else interpret
    r = 3
    k1, k2, c_in, c_out = w.shape
    assert k1 == k2, "winograd path requires square kernels"
    if in_layout is not None and in_layout.kind == "winograd" \
            and in_layout.m == m and k1 == in_layout.r:
        y = _conv_from_tiles(x, w, m, in_layout, dataflow, p1, p2,
                             interpret, epilogue, bias)
        return materialize(y, out_layout)
    x = restore(x, in_layout)
    h, w_dim, _ = x.shape
    if padding == "SAME":
        o1, o2 = h, w_dim
        pt_full = (k1 - 1) // 2
        pl_full = (k2 - 1) // 2
    else:
        o1, o2 = h - k1 + 1, w_dim - k2 + 1
        pt_full = pl_full = 0

    if k1 == r:
        return materialize(
            _conv_f_mr(x, w, m, o1, o2, pt_full, pl_full,
                       dataflow, p1, p2, interpret,
                       epilogue=epilogue, bias=bias), out_layout)

    # Multi-round: pad kernel to multiple of r and accumulate shifted rounds.
    rounds = -(-k1 // r)
    kp = rounds * r
    wp = jnp.pad(w, ((0, kp - k1), (0, kp - k2), (0, 0), (0, 0)))
    # out[y, x] = Σ_{ry,rx} Σ_{i,j<r} X[y+ry·r+i-pt, x+rx·r+j-pl]·W[ry·r+i, ...]
    # = Σ_rounds  F(m,r)-conv of X shifted by (ry·r, rx·r) with sub-kernel.
    xbig = jnp.pad(x, ((pt_full, kp), (pl_full, kp), (0, 0)))
    acc = jnp.zeros((o1, o2, c_out), x.dtype)
    for ry in range(rounds):
        for rx in range(rounds):
            sub = wp[ry * r:(ry + 1) * r, rx * r:(rx + 1) * r]
            xs = jax.lax.dynamic_slice(
                xbig, (ry * r, rx * r, 0),
                (o1 + r - 1, o2 + r - 1, c_in))
            # VALID conv of xs with sub gives exactly (o1, o2).
            acc = acc + _conv_f_mr(xs, sub, m, o1, o2, 0, 0,
                                   dataflow, p1, p2, interpret)
    return materialize(apply_epilogue(acc, epilogue, bias), out_layout)
