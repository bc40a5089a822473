"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cost_model import Dataflow
from repro.kernels.conv_im2col.ops import conv_im2col
from repro.kernels.conv_im2col.ref import (conv_ref, conv_via_toeplitz_ref,
                                           toeplitz_ref)
from repro.kernels.gemm.ops import batched_gemm, gemm
from repro.kernels.gemm.ref import batched_gemm_ref, gemm_ref
from repro.kernels.kn2row.ops import conv_kn2row
from repro.kernels.kn2row.ref import kn2row_ref
from repro.kernels.winograd.ops import conv_winograd
from repro.kernels.winograd.ref import winograd_ref

RNG = np.random.default_rng(0)


def rnd(*shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


# ------------------------------------------------------------------ GEMM
@pytest.mark.parametrize("mkn", [(62, 124, 64), (128, 128, 128),
                                 (200, 300, 100), (8, 512, 8),
                                 (1, 256, 131), (257, 129, 63)])
@pytest.mark.parametrize("df", list(Dataflow))
def test_gemm_all_dataflows_match_oracle(mkn, df):
    m, k, n = mkn
    a, b = rnd(m, k), rnd(k, n)
    out = gemm(a, b, dataflow=df, interpret=True)
    np.testing.assert_allclose(out, gemm_ref(a, b), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_dtypes(dtype):
    a, b = rnd(96, 160, dtype=dtype), rnd(160, 72, dtype=dtype)
    out = gemm(a, b, interpret=True)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(gemm_ref(a, b), np.float32),
                               rtol=tol, atol=tol)


def test_batched_gemm():
    a, b = rnd(5, 62, 40), rnd(5, 40, 70)
    out = batched_gemm(a, b, interpret=True)
    np.testing.assert_allclose(out, batched_gemm_ref(a, b),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------- im2col
# The last two run several Cin chunks and C_out blocks of 128 lanes, with
# channel counts that are not multiples of 128.
CASES = [(14, 14, 8, 16, 3, 3, 1, "SAME"), (28, 28, 4, 8, 5, 5, 1, "SAME"),
         (15, 15, 3, 8, 3, 3, 2, "SAME"), (14, 14, 8, 8, 1, 1, 1, "SAME"),
         (16, 16, 6, 10, 7, 7, 2, "SAME"), (14, 14, 8, 16, 3, 3, 1, "VALID"),
         (10, 10, 6, 10, 1, 7, 1, "SAME"), (9, 9, 200, 136, 3, 3, 2, "SAME"),
         (12, 11, 130, 260, 3, 3, 1, "VALID")]


@pytest.mark.parametrize("case", CASES)
def test_conv_im2col_matches_lax(case):
    h, w_, ci, co, k1, k2, s, pad = case
    x, w = rnd(h, w_, ci), rnd(k1, k2, ci, co)
    got = conv_im2col(x, w, stride=s, padding=pad, interpret=True)
    want = conv_ref(x, w, stride=s, padding=pad)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_toeplitz_layout_matches_eq2():
    x, w = rnd(9, 9, 4), rnd(3, 3, 4, 6)
    t = toeplitz_ref(x, 3, 3, 1, "SAME")
    assert t.shape == (81, 36)      # (O1O2, K1K2Cin)
    np.testing.assert_allclose(conv_via_toeplitz_ref(x, w),
                               conv_ref(x, w), rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------- kn2row
@pytest.mark.parametrize("case", CASES)
def test_conv_kn2row_matches_lax(case):
    h, w_, ci, co, k1, k2, s, pad = case
    x, w = rnd(h, w_, ci), rnd(k1, k2, ci, co)
    want = conv_ref(x, w, stride=s, padding=pad)
    np.testing.assert_allclose(kn2row_ref(x, w, stride=s, padding=pad),
                               want, rtol=1e-4, atol=1e-4)
    got = conv_kn2row(x, w, stride=s, padding=pad, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# -------------------------------------------------------------- winograd
@pytest.mark.parametrize("case", [(14, 14, 8, 16, 3, 2, "SAME"),
                                  (12, 12, 4, 8, 3, 4, "SAME"),
                                  (14, 14, 8, 16, 3, 2, "VALID"),
                                  (13, 11, 5, 7, 3, 2, "SAME"),
                                  (14, 14, 4, 8, 5, 2, "SAME"),
                                  (12, 12, 3, 6, 7, 2, "SAME"),
                                  (10, 10, 130, 140, 3, 4, "SAME"),
                                  (11, 9, 136, 12, 3, 2, "VALID")])
def test_conv_winograd_matches_lax(case):
    h, w_, ci, co, k, m, pad = case
    x, w = rnd(h, w_, ci), rnd(k, k, ci, co)
    want = conv_ref(x, w, stride=1, padding=pad)
    if k == 3:
        np.testing.assert_allclose(winograd_ref(x, w, m=m, padding=pad),
                                   want, rtol=2e-3, atol=2e-3)
    got = conv_winograd(x, w, m=m, padding=pad, interpret=True)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_all_three_algorithms_agree():
    """The executor invariant: any plan computes the same convolution."""
    x, w = rnd(12, 12, 6), rnd(3, 3, 6, 9)
    a = conv_im2col(x, w, interpret=True)
    b = conv_kn2row(x, w, interpret=True)
    c = conv_winograd(x, w, m=2, interpret=True)
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(a, c, rtol=2e-3, atol=2e-3)
