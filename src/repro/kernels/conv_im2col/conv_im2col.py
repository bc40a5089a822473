"""im2col convolution as an *implicit-GEMM* Pallas kernel.

The paper's im2col (§2.1.1) stretches input windows into a Toeplitz matrix in
DRAM and runs one big GEMM (Eq. 2). A mechanical port would materialize the
Toeplitz matrix in HBM — pure bandwidth waste on TPU. The TPU-native
adaptation gathers the windows **in VMEM** inside the kernel and feeds the
MXU directly: the Toeplitz tile exists only on-chip.

Each grid step holds one block of output rows, so the input arrives as the
matching band of input rows plus its halo (an element-offset block), never
the whole map. The GEMM's K dim (K1·K2·Cin) streams in Cin chunks of at
most 128 lanes, accumulated in a VMEM scratch — outputs and weights are
tiled on (output-rows × C_out), the (P_SA1, P_SA2) binding of the NS
dataflow. The input arrives phase-split by the stride
(``common.phase_split``), so every window is a unit-stride ``pl.ds`` read
of one phase.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (apply_epilogue, compiler_params,
                                  default_interpret)


def _conv_kernel(x_ref, w_ref, *rest, k1: int, k2: int, stride: int,
                 bo1: int, o2: int, cc: int, nkc: int, epilogue: str,
                 has_scale: bool = False, out_scale: float = None):
    """One grid step = (output-row block) × (C_out block) × (Cin chunk).

    Operand order after (x, w): [scale?][bias?] o_ref, acc_ref, toep_ref.
    Int8 inputs accumulate exactly in int32; the fused ``scale`` row
    dequantizes the GEMM block before bias/relu and ``out_scale``
    requantizes after.
    """
    rest = list(rest)
    scale_ref = rest.pop(0) if has_scale else None
    o_ref, acc_ref, toep_ref = rest[-3:]
    bias_ref = rest[0] if len(rest) == 4 else None
    kc = pl.program_id(2)

    @pl.when(kc == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The Toeplitz tile of this Cin chunk — VMEM-only (the whole point).
    for dk1 in range(k1):          # static unroll — k1,k2 are layer consts
        for dk2 in range(k2):
            tap = dk1 * k2 + dk2
            phase = (dk1 % stride) * stride + dk2 % stride
            patch = x_ref[phase, pl.ds(dk1 // stride, bo1),
                          pl.ds(dk2 // stride, o2), :]    # (bo1, o2, cc)
            toep_ref[:, tap * cc:(tap + 1) * cc] = patch.reshape(bo1 * o2, cc)
    acc_ref[...] += jnp.dot(toep_ref[...],
                            w_ref[...].reshape(k1 * k2 * cc, -1),
                            preferred_element_type=acc_ref.dtype)

    @pl.when(kc == nkc - 1)
    def _flush():
        # Epilogue on the GEMM output block while it is still VMEM-resident
        # — the §3 in-pipeline auxiliary unit (dequant/bias/relu/requant).
        acc = apply_epilogue(
            acc_ref[...], epilogue,
            bias_ref[0] if bias_ref is not None else None,
            scale=scale_ref[0] if scale_ref is not None else None,
            out_scale=out_scale)
        o_ref[...] = acc.reshape(bo1, o2, -1).astype(o_ref.dtype)


def conv_im2col_call(x: jax.Array, w: jax.Array, *, k1: int, k2: int,
                     stride: int, o1: int, o2: int, bo1: int, bc: int,
                     cc: int, interpret: Optional[bool] = None,
                     epilogue: str = "none",
                     bias: jax.Array = None, scale: jax.Array = None,
                     out_scale: float = None) -> jax.Array:
    """x: (S·S, Hq, Wq, Cin_p), the padded input phase-split by the
    stride, with every window of the (o1, o2) output in range;
    w: (K1·K2, Cin_p, Cout_p) → (o1, o2, Cout_p).

    Caller guarantees o1 % bo1 == 0, o2 a multiple of the dtype's sublane
    tile, Cout_p % bc == 0 and Cin_p % cc == 0 with cc == Cin_p or 128.
    """
    phases, hq, wq, c_in = x.shape
    taps, c_in_w, c_out = w.shape
    assert phases == stride * stride, (x.shape, stride)
    assert taps == k1 * k2 and c_in_w == c_in, (w.shape, k1, k2, c_in)
    assert c_out % bc == 0 and o1 % bo1 == 0 and c_in % cc == 0
    assert cc == c_in or cc % 128 == 0, (cc, c_in)
    rows_in = bo1 + (k1 - 1) // stride
    assert hq >= o1 + (k1 - 1) // stride and wq >= o2 + (k2 - 1) // stride
    nkc = c_in // cc
    quantized = x.dtype == jnp.int8
    acc_dtype = jnp.int32 if quantized else jnp.float32
    out_dtype = (jnp.int8 if out_scale is not None
                 else jnp.float32 if quantized else x.dtype)
    grid = (o1 // bo1, c_out // bc, nkc)
    in_specs = [
        # Output-row band plus halo: element offsets, so consecutive
        # bands overlap by the halo rows. Lane offsets must be provably
        # 128-aligned, hence the literal 0 for a single chunk.
        pl.BlockSpec((pl.Element(phases), pl.Element(rows_in),
                      pl.Element(wq), pl.Element(cc)),
                     (lambda i, j, kc: (0, i * bo1, 0, kc * cc))
                     if nkc > 1 else
                     (lambda i, j, kc: (0, i * bo1, 0, 0))),
        pl.BlockSpec((taps, cc, bc), lambda i, j, kc: (0, kc, j)),
    ]
    operands = [x, w]
    if scale is not None:
        assert scale.shape == (1, c_out), (scale.shape, c_out)
        in_specs.append(pl.BlockSpec((1, bc), lambda i, j, kc: (0, j)))
        operands.append(scale)
    if bias is not None:
        assert bias.shape == (1, c_out), (bias.shape, c_out)
        in_specs.append(pl.BlockSpec((1, bc), lambda i, j, kc: (0, j)))
        operands.append(bias)
    m_blk = bo1 * o2
    return pl.pallas_call(
        functools.partial(_conv_kernel, k1=k1, k2=k2, stride=stride,
                          bo1=bo1, o2=o2, cc=cc, nkc=nkc, epilogue=epilogue,
                          has_scale=scale is not None, out_scale=out_scale),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bo1, o2, bc), lambda i, j, kc: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((o1, o2, c_out), out_dtype),
        scratch_shapes=[pltpu.VMEM((m_blk, bc), acc_dtype),
                        pltpu.VMEM((m_blk, taps * cc), x.dtype)],
        compiler_params=compiler_params(
            [((phases, rows_in, wq, cc), x.dtype), ((taps, cc, bc), w.dtype),
             ((bo1, o2, bc), out_dtype), ((1, bc), jnp.float32),
             ((1, bc), jnp.float32)],
            [((m_blk, bc), acc_dtype), ((m_blk, taps * cc), x.dtype)]),
        interpret=default_interpret() if interpret is None else interpret,
    )(*operands)
