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

A stem — few input channels, few taps — would fill a lane tile with a
few channels per tap; ``conv_stem_call`` takes it instead: the image
arrives channel-major, is folded by the stride into channels in VMEM, and
every tap of its contraction packs into one dense K panel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cost_model import V5E
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


def _stem_kernel(x_ref, w_ref, *rest, stride: int, kq1: int, kq2: int,
                 rows: int, rp: int, cs: int, epilogue: str):
    """One grid step = ``rows`` output rows of one image, every C_out lane.

    The first step of an image folds it in VMEM, row-major:
    ``u[m, i·cs + ch, j] = x[c, s·i + ph, s·(128·m + j) + pw]`` for every
    folded channel ch = (ph, pw, c), made with transposes and sublane-
    strided reads and writes (TPU memory strides sublanes, never lanes).
    Every step then reads the cs folded channels of a folded row as whole
    tiles, rotates the column taps into place, stacks all taps of its
    rows into the transposed Toeplitz tile (K, rows·o2p) with aligned
    stores, runs one dense GEMM against the (C_out, K) weights, and
    transposes each output row back to (columns, C_out)."""
    bias_ref = rest[0] if len(rest) == 6 else None
    o_ref, s1_ref, s2_ref, u_ref, tt_ref = rest[-5:]
    s = stride
    c_in, hp, wp = x_ref.shape
    n_m = u_ref.shape[0]
    o2p = tt_ref.shape[1] // rows
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _fold():
        for ch in range(s * s * c_in, cs):
            for m in range(n_m):
                u_ref[m, pl.ds(ch, rp, stride=cs), :] = jnp.zeros(
                    (rp, 128), u_ref.dtype)
        for c in range(c_in):
            for pw in range(s):
                # Column phase pw of every input row: rows to lanes, a
                # strided read of the columns, and lanes back to rows.
                for k in range(hp // 128):
                    s1_ref[...] = x_ref[c, k * 128:(k + 1) * 128, :].T
                    cols = s1_ref[pl.ds(pw, wp // s, stride=s), :]
                    for m in range(n_m):
                        s2_ref[m, k * 128:(k + 1) * 128, :] = \
                            cols[m * 128:(m + 1) * 128].T
                for ph in range(s):
                    ch = (ph * s + pw) * c_in + c
                    for m in range(n_m):
                        u_ref[m, pl.ds(ch, rp, stride=cs), :] = \
                            s2_ref[m, pl.ds(ph, rp, stride=s), :]

    for r in range(rows):           # static unroll — layer constants
        for q1 in range(kq1):
            at_row = (i * rows + r + q1) * cs
            band = jnp.concatenate(
                [u_ref[m, pl.ds(at_row, cs), :] for m in range(n_m)], axis=1)
            for q2 in range(kq2):
                tap = band if q2 == 0 else pltpu.roll(band, n_m * 128 - q2, 1)
                at = (q1 * kq2 + q2) * cs
                tt_ref[at:at + cs, r * o2p:(r + 1) * o2p] = tap[:, :o2p]
    acc = jnp.dot(w_ref[...], tt_ref[...],
                  preferred_element_type=jnp.float32)   # (C_out, rows·o2p)
    for r in range(rows):
        y = apply_epilogue(acc[:, r * o2p:(r + 1) * o2p].T[:o_ref.shape[1]],
                           epilogue,
                           bias_ref[0] if bias_ref is not None else None)
        o_ref[r] = y.astype(o_ref.dtype)


def _stem_vmem(c_in: int, hp: int, wp: int, c_out: int, k: int, dtype, *,
               stride: int, taps: int, rows: int, o2: int):
    """The stem kernel's VMEM: its scratch, and the Mosaic parameters that
    fit that scratch, the pipelined blocks and the GEMM's f32 result."""
    rp, n_m = hp // stride, wp // stride // 128
    o2p = -(-o2 // 128) * 128
    scratch = [((wp, 128), dtype), ((n_m, hp, 128), dtype),
               ((n_m, rp * (k // taps), 128), dtype), ((k, rows * o2p), dtype)]
    blocks = [((c_in, hp, wp), dtype), ((c_out, k), dtype),
              ((rows, o2, c_out), dtype), ((1, c_out), jnp.float32)]
    return scratch, compiler_params(
        blocks, scratch + [((c_out, rows * o2p), jnp.float32)])


def stem_fits_vmem(c_in: int, hp: int, wp: int, c_out: int, k: int, dtype,
                   *, stride: int, taps: int, rows: int, o2: int) -> bool:
    """Whether the stem kernel, which holds a whole image in VMEM, fits
    the chip's: the limit ``compiler_params`` asks for stays below it."""
    _, params = _stem_vmem(c_in, hp, wp, c_out, k, dtype, stride=stride,
                           taps=taps, rows=rows, o2=o2)
    return params.vmem_limit_bytes < V5E.vmem_bytes


def conv_stem_call(x: jax.Array, wt: jax.Array, *, stride: int, kq1: int,
                   kq2: int, o1: int, o2: int, rows: int,
                   interpret: Optional[bool] = None,
                   epilogue: str = "none",
                   bias: jax.Array = None) -> jax.Array:
    """The dense stem: a conv whose whole contraction, its stride folded
    into channels, fits one K panel (``ops.dense_stem``).

    x: (Cin, s·Rp, s·L), the padded input channel-major (rows, then
    columns on the lanes), with Rp and L multiples of 128, Rp ≥ o1 + kq1
    - 1 and L ≥ o2p + kq2 - 1 where o2p = ceil(o2 / 128) · 128;
    wt: (C_out_p, kq1·kq2·Cs), the re-packed weights in (row tap, column
    tap, ph, pw, c) order, each tap's Cin·s² channels zero-padded to Cs, a
    multiple of 8, and transposed → (o1, o2, C_out_p).

    Caller guarantees o1 % rows == 0, o2 a multiple of 8 and C_out_p a
    multiple of 128.
    """
    c_in, hp, wp = x.shape
    c_out, k = wt.shape
    s = stride
    rp, lanes = hp // s, wp // s
    o2p = -(-o2 // 128) * 128
    cs = k // (kq1 * kq2)
    assert hp % (128 * s) == 0 and wp % (128 * s) == 0, x.shape
    assert cs % 8 == 0 and cs >= c_in * s * s, (cs, c_in, s)
    assert rp >= o1 + kq1 - 1 and lanes >= o2p + kq2 - 1, (x.shape, o1, o2)
    assert o1 % rows == 0 and c_out % 128 == 0, (o1, rows, c_out)
    in_specs = [
        # The whole image, fetched once for its row steps.
        pl.BlockSpec((c_in, hp, wp), lambda i: (0, 0, 0)),
        pl.BlockSpec((c_out, k), lambda i: (0, 0)),
    ]
    operands = [x, wt]
    if bias is not None:
        assert bias.shape == (1, c_out), (bias.shape, c_out)
        in_specs.append(pl.BlockSpec((1, c_out), lambda i: (0, 0)))
        operands.append(bias)
    scratch, params = _stem_vmem(c_in, hp, wp, c_out, k, x.dtype, stride=s,
                                 taps=kq1 * kq2, rows=rows, o2=o2)
    return pl.pallas_call(
        functools.partial(_stem_kernel, stride=s, kq1=kq1, kq2=kq2,
                          rows=rows, rp=rp, cs=cs, epilogue=epilogue),
        grid=(o1 // rows,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows, o2, c_out), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((o1, o2, c_out), x.dtype),
        scratch_shapes=[pltpu.VMEM(shp, dt) for shp, dt in scratch],
        compiler_params=params,
        interpret=default_interpret() if interpret is None else interpret,
        name="conv_stem_dense",
    )(*operands)
