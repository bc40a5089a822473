"""kn2row convolution: K1·K2 unit-conv GEMMs + Pad-and-Accumulate (§2.1.2).

Phase 1 ("unit-CONV GEMM", Eq. 3): each (k1, k2) kernel offset is a 1×1
convolution — a (H1H2, Cin) × (Cin, Cout) GEMM. We run all K1K2 of them as
one batched Pallas GEMM whose input block index map ignores the batch
coordinate, so the feature-map block is fetched once and stays VMEM-resident
across offsets (the paper's pipelining of the two phases).

Phase 2 ("Pad-and-Accumulate", Eq. 4): each intermediate patch p_{k1,k2} is
shifted by its offset w.r.t. the center patch and Hadamard-added. The grid
walks (output-row block, channel block, kernel row): each step fetches the
K2 patches of one kernel row as an element-offset block already shifted to
that row, and adds their K2 column-shifted windows into an output block
that stays resident in VMEM across kernel rows — the accumulation buffer of
§3.1, whose partial sums never leave VMEM. The feature map is phase-split
by the stride before phase 1 (``common.phase_split``), so the patches come
out phase-split too and every shifted window is a unit-stride read.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (apply_epilogue, compiler_params,
                                  default_interpret)


# ---------------------------------------------------------------------------
# Phase 1 — unit-conv GEMMs, batched over kernel offsets.
# ---------------------------------------------------------------------------

def unit_conv_gemms(x2d: jax.Array, w: jax.Array, *, bm: int, bn: int,
                    bk: int, interpret: Optional[bool] = None) -> jax.Array:
    """x2d: (H1H2, Cin); w: (K1K2, Cin, Cout) → p: (K1K2, H1H2, Cout)."""
    m, k = x2d.shape
    g, k2, n = w.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0
    nk = k // bk
    # Int8 phase 1 keeps exact int32 partial products; dequantization
    # waits for the phase-2 flush (scale is constant across offsets).
    quantized = x2d.dtype == jnp.int8
    acc_dtype = jnp.int32 if quantized else jnp.float32
    out_dtype = jnp.int32 if quantized else x2d.dtype

    def kernel(x_ref, w_ref, o_ref, acc_ref):
        kk = pl.program_id(3)

        @pl.when(kk == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                                preferred_element_type=acc_dtype)

        @pl.when(kk == nk - 1)
        def _flush():
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid=(g, m // bm, n // bn, nk),
        in_specs=[
            # Note: index map ignores g → the X block is re-used across all
            # K1K2 unit convolutions without re-fetch.
            pl.BlockSpec((bm, bk), lambda gg, i, j, kk: (i, kk)),
            pl.BlockSpec((1, bk, bn), lambda gg, i, j, kk: (gg, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda gg, i, j, kk: (gg, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=compiler_params(
            [((bm, bk), x2d.dtype), ((bk, bn), w.dtype),
             ((bm, bn), out_dtype)],
            [((bm, bn), acc_dtype)]),
        interpret=default_interpret() if interpret is None else interpret,
    )(x2d, w)


# ---------------------------------------------------------------------------
# Phase 2 — Pad-and-Accumulate.
# ---------------------------------------------------------------------------

def pad_accumulate(p: jax.Array, *, k1: int, k2: int, o1: int, o2: int,
                   bo1: int, stride: int = 1,
                   interpret: Optional[bool] = None, epilogue: str = "none",
                   bias: jax.Array = None, scale: jax.Array = None,
                   out_scale: float = None) -> jax.Array:
    """p: (K1, K2, S·S, Hq, Wq, C) — the unit-conv patches of the padded,
    phase-split input; returns (o1, o2, C).

    Eq. 4: z[y, x] = Σ_{k1,k2} p_{k1,k2}[S·y + k1, S·x + k2] over the
    padded map, i.e. phase ((k1 % S)·S + k2 % S) at (y + k1 // S,
    x + k2 // S). As the final kn2row stage, it owns the fused epilogue:
    the accumulated output streams through ReLU/bias at the flush, before
    ever leaving VMEM. Caller guarantees o1 % bo1 == 0, o2 a multiple of
    the sublane tile, and C a multiple of 128 or at most 128.

    Int8 path: ``p`` holds exact int32 unit-conv partials; accumulation
    stays int32 and the flush dequantizes with ``scale`` ((1, C) per-
    output-channel), then bias/relu, then the optional ``out_scale``
    requantize — the whole chain in one VMEM-resident pass.
    """
    p_k1, p_k2, phases, hq, wq, c = p.shape
    assert (p_k1, p_k2) == (k1, k2), (p.shape, k1, k2)
    assert phases == stride * stride, (p.shape, stride)
    assert hq >= o1 + (k1 - 1) // stride and wq >= o2 + (k2 - 1) // stride, \
        (p.shape, o1, o2)
    assert o1 % bo1 == 0, (o1, bo1)
    cb = min(c, 128)
    assert c % cb == 0, c
    quantized = p.dtype == jnp.int32
    acc_dtype = jnp.int32 if quantized else jnp.float32
    out_dtype = (jnp.int8 if out_scale is not None
                 else jnp.float32 if quantized else p.dtype)
    has_scale = scale is not None

    def kernel(p_ref, *rest):
        rest = list(rest)
        scale_ref = rest.pop(0) if has_scale else None
        o_ref, acc_ref = rest[-2], rest[-1]
        bias_ref = rest[0] if len(rest) == 3 else None
        dk1 = pl.program_id(2)

        @pl.when(dk1 == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # The block already sits at this kernel row's shift; the K2
        # column shifts are static windows of it.
        for dk2 in range(k2):
            acc_ref[...] += p_ref[0, dk2, dk2 % stride, :,
                                  pl.ds(dk2 // stride, o2), :
                                  ].astype(acc_dtype)

        @pl.when(dk1 == k1 - 1)
        def _flush():
            acc = apply_epilogue(
                acc_ref[...], epilogue,
                bias_ref[0] if bias_ref is not None else None,
                scale=scale_ref[0] if scale_ref is not None else None,
                out_scale=out_scale)
            o_ref[...] = acc.astype(o_ref.dtype)

    def p_index(i, j, dk1):
        # Kernel row dk1 reads the stride phases of row (dk1 % S), shifted
        # down by dk1 // S rows. Lane offsets must be provably 128-aligned:
        # literal 0 for a single channel block.
        return (dk1, 0, (dk1 % stride) * stride, i * bo1 + dk1 // stride, 0,
                j * cb if c > cb else 0)

    in_specs = [pl.BlockSpec(
        tuple(pl.Element(d) for d in (1, k2, stride, bo1, wq, cb)),
        p_index)]
    operands = [p]
    if scale is not None:
        assert scale.shape == (1, c), (scale.shape, c)
        in_specs.append(pl.BlockSpec((1, cb), lambda i, j, dk1: (0, j)))
        operands.append(scale)
    if bias is not None:
        assert bias.shape == (1, c), (bias.shape, c)
        in_specs.append(pl.BlockSpec((1, cb), lambda i, j, dk1: (0, j)))
        operands.append(bias)
    return pl.pallas_call(
        kernel,
        grid=(o1 // bo1, c // cb, k1),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bo1, o2, cb), lambda i, j, dk1: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((o1, o2, c), out_dtype),
        scratch_shapes=[pltpu.VMEM((bo1, o2, cb), acc_dtype)],
        compiler_params=compiler_params(
            [((k2, stride, bo1, wq, cb), p.dtype),
             ((bo1, o2, cb), out_dtype), ((1, cb), jnp.float32),
             ((1, cb), jnp.float32)],
            [((bo1, o2, cb), acc_dtype)]),
        interpret=default_interpret() if interpret is None else interpret,
    )(*operands)
