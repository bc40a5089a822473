"""Winograd F(m,r) convolution kernels (§2.1.3, Eq. 5/6).

Pipeline (the paper's Linear Transform Modules → Pallas kernels):
  1. input transform   V[ξν, tile, c]  = (Bᵀ d B)           — Pallas kernel
  2. kernel transform  U[ξν, c, k]     = (G g Gᵀ)           — precomputed
     (amortized across inferences, exactly as the FPGA design pre-loads it)
  3. (m+r-1)² independent GEMMs M = V·U (Eq. 6)             — batched Pallas GEMM
  4. output transform  Y = Aᵀ M A, tiles scattered back      — Pallas kernel

Layouts follow §3.3: V and M live in the "scattered" Winograd layout
(T², tiles_y, txp, C) — elements at the same intra-tile position adjacent,
the tile columns padded to whole sublane tiles — so the GEMM batch dim is
the intra-tile coordinate (ξ, ν).

Kernels larger than r×r run in ceil(K1/r)·ceil(K2/r) rounds of shifted
r×r sub-kernels, accumulating outputs — §6.1.2's "K1K2/3² rounds of
Winograd ... resulting in severe transformation overheads" is exactly this
path, and the cost model prices it the same way.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.common import (apply_epilogue, ceil_to, compiler_params,
                                  default_interpret, sublanes)

# ---------------------------------------------------------------------------
# Transform matrices (Lavin & Gray). F(2,3) uses only ±1, ±1/2 — the paper
# notes these reduce to shift-adds on FPGA; on TPU they are VPU constants.
# ---------------------------------------------------------------------------

_BT = {
    (2, 3): np.array([[1, 0, -1, 0],
                      [0, 1, 1, 0],
                      [0, -1, 1, 0],
                      [0, 1, 0, -1]], np.float32),
    (4, 3): np.array([[4, 0, -5, 0, 1, 0],
                      [0, -4, -4, 1, 1, 0],
                      [0, 4, -4, -1, 1, 0],
                      [0, -2, -1, 2, 1, 0],
                      [0, 2, -1, -2, 1, 0],
                      [0, 4, 0, -5, 0, 1]], np.float32),
}
_G = {
    (2, 3): np.array([[1, 0, 0],
                      [0.5, 0.5, 0.5],
                      [0.5, -0.5, 0.5],
                      [0, 0, 1]], np.float32),
    (4, 3): np.array([[1 / 4, 0, 0],
                      [-1 / 6, -1 / 6, -1 / 6],
                      [-1 / 6, 1 / 6, -1 / 6],
                      [1 / 24, 1 / 12, 1 / 6],
                      [1 / 24, -1 / 12, 1 / 6],
                      [0, 0, 1]], np.float32),
}
_AT = {
    (2, 3): np.array([[1, 1, 1, 0],
                      [0, 1, -1, -1]], np.float32),
    (4, 3): np.array([[1, 1, 1, 1, 1, 0],
                      [0, 1, -1, 2, -2, 0],
                      [0, 1, 1, 4, 4, 0],
                      [0, 1, -1, 8, -8, 1]], np.float32),
}


def matrices(m: int, r: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    if (m, r) not in _BT:
        raise ValueError(f"F({m},{r}) not supported; have {_BT.keys()}")
    return _BT[(m, r)], _G[(m, r)], _AT[(m, r)]


def transform_kernel_weights(w: jax.Array, m: int, r: int) -> jax.Array:
    """U[ξν, Cin, Cout] = G g Gᵀ — the offline kernel transform."""
    _, g_mat, _ = matrices(m, r)
    g_ = jnp.asarray(g_mat)
    # w: (r, r, Cin, Cout) → (T, T, Cin, Cout) → (T², Cin, Cout)
    u = jnp.einsum("ti,ijco,uj->tuco", g_, w.astype(jnp.float32), g_)
    t = m + r - 1
    return u.reshape(t * t, *w.shape[2:])


# ---------------------------------------------------------------------------
# The transforms as VPU multiply-adds over the constant matrices: each
# Bᵀ d B (or Aᵀ M A) is two passes of 1-D combinations with the matrix
# entries as static scalars (zeros skipped), applied to whole stacks of
# (tiles_x, C) slabs — one slab per intra-tile position.
# ---------------------------------------------------------------------------

def _lincomb(coeffs, slabs):
    """Σ c·slab over the nonzero constants ``coeffs``."""
    acc = None
    for c, slab in zip(coeffs, slabs):
        c = float(c)
        if c == 0.0:
            continue
        term = slab if c == 1.0 else -slab if c == -1.0 else c * slab
        acc = term if acc is None else acc + term
    return acc


def _transform_2d(mat: np.ndarray, slabs):
    """Separable ``mat · D · matᵀ``: ``slabs[j]`` is D[:, j] stacked on a
    leading axis (shape (n, tiles_x, C)); returns ``out[u]``, the row
    out[u, :] stacked the same way."""
    cols = jnp.stack([_lincomb(row, slabs) for row in mat])   # [v, i]
    return [_lincomb(row, [cols[:, i] for i in range(cols.shape[1])])
            for row in mat]


def _channel_block(c: int) -> int:
    """Transforms are channel-wise: blocks of 128 lanes, or all of a
    narrower channel dim."""
    assert c <= 128 or c % 128 == 0, c
    return min(c, 128)


def _lane_index(c: int, cb: int):
    """Element-offset lane index of channel block j: Mosaic must prove it
    128-aligned, so a single block uses the literal 0."""
    return (lambda j: j * cb) if c > cb else (lambda j: 0)


# ---------------------------------------------------------------------------
# 1. Input transform: d tiles → V (scattered layout).
# ---------------------------------------------------------------------------

def input_transform(x: jax.Array, *, m: int, r: int, tiles_y: int,
                    tiles_x: int, interpret: Optional[bool] = None
                    ) -> jax.Array:
    """x: (Hp, Wp, C) padded so Hp ≥ tiles_y·m + r - 1 (same for W), C at
    most 128 or a multiple of 128. Returns V: (T², tiles_y, txp, C), the
    tile columns padded to txp, a whole number of sublane tiles.

    One grid step = one tile row: its T input rows arrive as an
    element-offset block. The columns are phase-split by m outside the
    kernel, so tile column tx at offset b reads phase b % m at tx + b // m
    — each (a, b) slab of all tiles of the row is one unit-stride load.
    """
    t = m + r - 1
    hp, wp, c = x.shape
    txp = ceil_to(tiles_x, sublanes(x.dtype))
    wq = txp + (t - 1) // m
    x = jnp.pad(x, ((0, 0), (0, max(0, wq * m - wp)), (0, 0)))[:, :wq * m]
    xw = x.reshape(hp, wq, m, c).transpose(0, 2, 1, 3)     # (Hp, m, wq, C)
    bt = matrices(m, r)[0]
    cb = _channel_block(c)
    lane = _lane_index(c, cb)

    def kernel(x_ref, v_ref):
        # d[:, b]: column offset b of all T rows of the tile row.
        d = [x_ref[:, b % m, pl.ds(b // m, txp), :].astype(jnp.float32)
             for b in range(t)]
        for u, v in enumerate(_transform_2d(bt, d)):
            v_ref[u * t:(u + 1) * t, 0] = v.astype(v_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid=(tiles_y, c // cb),
        in_specs=[pl.BlockSpec(
            tuple(pl.Element(d) for d in (t, m, wq, cb)),
            lambda i, j: (i * m, 0, 0, lane(j)))],
        out_specs=pl.BlockSpec((t * t, 1, txp, cb), lambda i, j: (0, i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((t * t, tiles_y, txp, c), x.dtype),
        compiler_params=compiler_params(
            [((t, m, wq, cb), x.dtype), ((t * t, 1, txp, cb), x.dtype)],
            [((3 * t * t, txp, cb), jnp.float32)]),
        interpret=default_interpret() if interpret is None else interpret,
    )(xw)


def input_transform_tiles(tiles: jax.Array, *, m: int, r: int, tiles_y: int,
                          tiles_x: int, interpret: Optional[bool] = None
                          ) -> jax.Array:
    """Matched-layout input transform: ``tiles`` (tiles_y·tiles_x, T, T, C)
    already sit in the scattered Winograd layout (the producer stored them
    — Table 2 row 4's streaming load), so no spatial re-gather happens
    here; each tile goes straight through Bᵀ d B.
    Returns V: (T², tiles_y, txp, C), laid out as ``input_transform``'s."""
    t = m + r - 1
    n, _, _, c = tiles.shape
    assert n == tiles_y * tiles_x, (n, tiles_y, tiles_x)
    txp = ceil_to(tiles_x, sublanes(tiles.dtype))
    bt = matrices(m, r)[0]
    cb = _channel_block(c)

    def kernel(t_ref, v_ref):
        d = [jnp.stack([t_ref[:, a, b, :] for a in range(t)]
                       ).astype(jnp.float32) for b in range(t)]
        for u, v in enumerate(_transform_2d(bt, d)):
            v_ref[u * t:(u + 1) * t, 0] = jnp.pad(
                v, ((0, 0), (0, txp - tiles_x), (0, 0))).astype(v_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid=(tiles_y, c // cb),
        in_specs=[pl.BlockSpec((tiles_x, t, t, cb),
                               lambda i, j: (i, 0, 0, j))],
        out_specs=pl.BlockSpec((t * t, 1, txp, cb), lambda i, j: (0, i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((t * t, tiles_y, txp, c),
                                       tiles.dtype),
        compiler_params=compiler_params(
            [((tiles_x, t, t, cb), tiles.dtype),
             ((t * t, 1, txp, cb), tiles.dtype)],
            [((3 * t * t, txp, cb), jnp.float32)]),
        interpret=default_interpret() if interpret is None else interpret,
    )(tiles)


# ---------------------------------------------------------------------------
# 4. Output transform: M (scattered) → spatial Y.
# ---------------------------------------------------------------------------

def output_transform(m_arr: jax.Array, *, m: int, r: int,
                     interpret: Optional[bool] = None,
                     epilogue: str = "none",
                     bias: jax.Array = None) -> jax.Array:
    """m_arr: (T², tiles_y, txp, C) → (tiles_y·m, txp·m, C).

    As the final Winograd stage it owns the fused epilogue: Y = Aᵀ M A flows
    through ReLU/bias while still VMEM-resident. ``bias`` (if given): (1, C).
    The kernel emits each tile row as (m, m, txp, C) — one slab per
    intra-tile output position (q, p) — and the interleave into spatial
    rows and columns is a transpose outside it.
    """
    t = m + r - 1
    tt, tiles_y, txp, c = m_arr.shape
    assert tt == t * t, (m_arr.shape, t)
    at = matrices(m, r)[2]
    cb = _channel_block(c)

    def kernel(m_ref, *rest):
        if len(rest) == 2:
            bias_ref, y_ref = rest
        else:
            (y_ref,), bias_ref = rest, None
        # Rows M[u, :] are contiguous; passing them as the columns of Mᵀ
        # yields (Aᵀ M A)ᵀ, i.e. y[q] = Y[:, q] — stored q-major.
        rows = [m_ref[u * t:(u + 1) * t, 0].astype(jnp.float32)
                for u in range(t)]
        for q, y in enumerate(_transform_2d(at, rows)):
            y = apply_epilogue(y, epilogue,
                               bias_ref[0] if bias_ref is not None else None)
            y_ref[q] = y.astype(y_ref.dtype)

    in_specs = [pl.BlockSpec((t * t, 1, txp, cb), lambda i, j: (0, i, 0, j))]
    operands = [m_arr]
    if bias is not None:
        assert bias.shape == (1, c), (bias.shape, c)
        in_specs.append(pl.BlockSpec((1, cb), lambda i, j: (0, j)))
        operands.append(bias)
    y = pl.pallas_call(
        kernel,
        grid=(tiles_y, c // cb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m, m, txp, cb), lambda i, j: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((tiles_y * m, m, txp, c),
                                       m_arr.dtype),
        compiler_params=compiler_params(
            [((t * t, 1, txp, cb), m_arr.dtype), ((1, cb), jnp.float32),
             ((m, m, txp, cb), m_arr.dtype)],
            [((2 * t * t, txp, cb), jnp.float32)]),
        interpret=default_interpret() if interpret is None else interpret,
    )(*operands)
    y = y.reshape(tiles_y, m, m, txp, c).transpose(0, 2, 3, 1, 4)
    return y.reshape(tiles_y * m, txp * m, c)
