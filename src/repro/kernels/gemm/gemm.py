"""Dataflow-switchable tiled GEMM — the paper's Computing Unit on the MXU.

§3.2 of the paper binds the two physical systolic-array dims (P_SA1, P_SA2)
to different GEMM dims per dataflow; the third dim streams:

    NS: (a → P_SA1, c → P_SA2), b streams   — output-stationary
    WS: (b → P_SA1, c → P_SA2), a streams   — weight block resident
    IS: (b → P_SA1, a → P_SA2), c streams   — input block resident

On TPU the virtual array is a Pallas block: the dataflow chooses which two
GEMM dims carry the (p1, p2) block shape — and therefore which dims suffer
ceil-division padding waste (Eq. 9) — while the streamed dim is tiled at the
native 128 granularity. One kernel body serves all three; the binding
happens in ops.py.

Grid is (i, j, k) with k innermost (contiguous output-block revisits, as
Pallas TPU requires); a VMEM f32 scratch accumulates partial products, which
is exactly the stall-free accumulate-in-place of the paper's PE design.
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


def _gemm_kernel(a_ref, b_ref, *rest, nk: int, epilogue: str,
                 has_scale: bool = False, out_scale: float = None):
    """One kernel body for the bf16 and int8 paths.

    Operand order after (a, b): [scale?][bias?] o_ref, acc_ref. The int8
    path accumulates exactly in an int32 scratch (``preferred_element_type``
    matches the scratch dtype), then the flush dequantizes with the fused
    per-channel ``scale`` row, applies bias/relu, and optionally
    requantizes at the static ``out_scale`` — one VMEM round trip total.
    """
    rest = list(rest)
    scale_ref = rest.pop(0) if has_scale else None
    o_ref, acc_ref = rest[-2], rest[-1]
    bias_ref = rest[0] if len(rest) == 3 else None
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=acc_ref.dtype)

    @pl.when(k == nk - 1)
    def _flush():
        acc = apply_epilogue(
            acc_ref[...], epilogue,
            bias_ref[0] if bias_ref is not None else None,
            scale=scale_ref[0] if scale_ref is not None else None,
            out_scale=out_scale)
        o_ref[...] = acc.astype(o_ref.dtype)


def gemm_pallas(a: jax.Array, b: jax.Array, *, bm: int, bn: int, bk: int,
                interpret: Optional[bool] = None, out_dtype=None,
                epilogue: str = "none",
                bias: jax.Array = None,
                scale: jax.Array = None,
                out_scale: float = None) -> jax.Array:
    """C = epilogue(A @ B [+ bias]) with explicit (bm, bn, bk) VMEM tiling.

    The epilogue is applied in-kernel at the accumulator flush — the output
    block streams through the auxiliary unit (§3) before ever leaving VMEM.
    Caller must pre-pad so M % bm == N % bn == K % bk == 0 (ops.py does);
    ``bias`` (if given) must be pre-padded to (1, N).

    Int8 path: when A/B are int8 the scratch accumulator is int32 (exact),
    ``scale`` (pre-padded (1, N), per-output-channel in_scale·w_scale)
    dequantizes at the flush, and a non-None ``out_scale`` requantizes the
    epilogue result to an int8 output.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (m, n, k, bm, bn, bk)
    nk = k // bk
    quantized = a.dtype == jnp.int8
    acc_dtype = jnp.int32 if quantized else jnp.float32
    if out_dtype is None:
        out_dtype = (jnp.int8 if out_scale is not None
                     else jnp.float32 if quantized else a.dtype)

    grid = (m // bm, n // bn, nk)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    operands = [a, b]
    if scale is not None:
        assert scale.shape == (1, n), (scale.shape, n)
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(scale)
    if bias is not None:
        assert bias.shape == (1, n), (bias.shape, n)
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(bias)
    return pl.pallas_call(
        functools.partial(_gemm_kernel, nk=nk, epilogue=epilogue,
                          has_scale=scale is not None, out_scale=out_scale),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=compiler_params(
            [((bm, bk), a.dtype), ((bk, bn), b.dtype), ((bm, bn), out_dtype),
             ((1, bn), jnp.float32), ((1, bn), jnp.float32)],
            [((bm, bn), acc_dtype)]),
        interpret=default_interpret() if interpret is None else interpret,
    )(*operands)


def batched_gemm_pallas(a: jax.Array, b: jax.Array, *, bm: int, bn: int,
                        bk: int, interpret: Optional[bool] = None,
                        out_dtype=None,
                        epilogue: str = "none",
                        bias: jax.Array = None) -> jax.Array:
    """C[g] = epilogue(A[g] @ B[g] [+ bias]) — used for the (m+r-1)^2
    independent Winograd GEMMs (Eq. 6): the transform-space Hadamard products
    batched over tile position. ``bias`` (if given) is (1, N), shared across
    the batch dim."""
    g, m, k = a.shape
    g2, k2, n = b.shape
    assert g == g2 and k == k2
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    nk = k // bk
    out_dtype = out_dtype or a.dtype

    def kernel(a_ref, b_ref, *rest):
        if len(rest) == 3:
            bias_ref, o_ref, acc_ref = rest
        else:
            (o_ref, acc_ref), bias_ref = rest, None
        kk = pl.program_id(3)

        @pl.when(kk == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(a_ref[0], b_ref[0],
                                preferred_element_type=jnp.float32)

        @pl.when(kk == nk - 1)
        def _flush():
            acc = apply_epilogue(acc_ref[...], epilogue,
                                 bias_ref[0] if bias_ref is not None else None)
            o_ref[0] = acc.astype(o_ref.dtype)

    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda gg, i, j, kk: (gg, i, kk)),
        pl.BlockSpec((1, bk, bn), lambda gg, i, j, kk: (gg, kk, j)),
    ]
    operands = [a, b]
    if bias is not None:
        assert bias.shape == (1, n), (bias.shape, n)
        in_specs.append(pl.BlockSpec((1, bn), lambda gg, i, j, kk: (0, j)))
        operands.append(bias)
    return pl.pallas_call(
        kernel,
        grid=(g, m // bm, n // bn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda gg, i, j, kk: (gg, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=compiler_params(
            [((bm, bk), a.dtype), ((bk, bn), b.dtype), ((bm, bn), out_dtype),
             ((1, bn), jnp.float32)],
            [((bm, bn), jnp.float32)]),
        interpret=default_interpret() if interpret is None else interpret,
    )(*operands)
