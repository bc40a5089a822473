"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cost_model import Dataflow
from repro.cnn.models import googlenet
from repro.core.layouts import LayoutSpec
from repro.kernels.conv_im2col.ops import conv_im2col, dense_stem
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
# The first row's multi-tap convs have so few channels that im2col takes
# the dense stem route; the 1x1 conv and the rows after it take the
# chunked kernel: stride 1 with Cin < 128 (GoogleNet's 5x5 and 3x3 convs,
# Inception-v4's 1x7), stride 2 phase-split, and, in the last two, several
# Cin chunks and C_out blocks of 128 lanes with channel counts that are
# not multiples of 128.
CASES = [(14, 14, 8, 16, 3, 3, 1, "SAME"), (28, 28, 4, 8, 5, 5, 1, "SAME"),
         (15, 15, 3, 8, 3, 3, 2, "SAME"), (14, 14, 8, 8, 1, 1, 1, "SAME"),
         (16, 16, 6, 10, 7, 7, 2, "SAME"), (14, 14, 8, 16, 3, 3, 1, "VALID"),
         (10, 10, 6, 10, 1, 7, 1, "SAME"),
         (28, 28, 16, 32, 5, 5, 1, "SAME"), (14, 14, 32, 16, 3, 3, 1, "VALID"),
         (10, 10, 40, 10, 1, 7, 1, "SAME"), (15, 15, 40, 8, 3, 3, 2, "SAME"),
         (9, 9, 200, 136, 3, 3, 2, "SAME"),
         (12, 11, 130, 260, 3, 3, 1, "VALID")]
CHUNKED = CASES[7:]


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


# ---------------------------------------------------------- dense stem
# (H, Cin, Cout, K, stride, padding): GoogleNet's conv1 at 224 and
# Inception-v4's first stem conv at 299, at their published shapes.
STEMS = {"googlenet_conv1": (224, 3, 64, 7, 2, "SAME"),
         "inception_v4_stem": (299, 3, 32, 3, 2, "VALID")}
BINDINGS = [(Dataflow.NS, 128, 128), (Dataflow.WS, 256, 128),
            (Dataflow.IS, 512, 256)]


@pytest.mark.parametrize("epilogue", ["none", "bias_relu"])
@pytest.mark.parametrize("batch", [None, 2])
@pytest.mark.parametrize("stem", sorted(STEMS))
def test_dense_stem_matches_lax(stem, batch, epilogue):
    """The dense stem route computes the conv, and every (dataflow, p1,
    p2) binding gives the same bits (the overlay's §3 invariant)."""
    h, ci, co, k, s, pad = STEMS[stem]
    assert dense_stem((h, h, ci), (k, k, ci, co), s, pad, jnp.float32)
    x = rnd(h, h, ci) if batch is None else rnd(batch, h, h, ci)
    w, b = rnd(k, k, ci, co), rnd(co)
    want = conv_ref(x, w, stride=s, padding=pad)
    if epilogue == "bias_relu":
        want = jnp.maximum(want + b, 0)
    got = [conv_im2col(x, w, stride=s, padding=pad, dataflow=df, p1=p1,
                       p2=p2, interpret=True, epilogue=epilogue, bias=b)
           for df, p1, p2 in BINDINGS]
    assert got[0].shape == want.shape
    np.testing.assert_allclose(got[0], want, rtol=1e-4, atol=1e-4)
    for other in got[1:]:
        np.testing.assert_array_equal(other, got[0])


def test_dense_stem_routes_googlenet_conv1_alone():
    """Of GoogleNet-224's 57 convs only conv1 takes the dense route: the
    5x5 convs (Cin 16-48) and the 3x3 convs (Cin >= 64) contract too many
    lanes, and the 1x1 convs have one tap. An int8 or bfloat16 input, a
    Toeplitz input layout and an image too large for VMEM keep the chunked
    path."""
    g = googlenet(res=224)
    convs = {g.nodes[n].name: g.nodes[n].conv for n in g.topo_order()
             if g.nodes[n].kind.value == "conv"}
    assert len(convs) == 57
    routed = [name for name, m in convs.items()
              if dense_stem((m.h1, m.h2, m.c_in), (m.k1, m.k2, m.c_in, m.c_out),
                            m.stride, m.pad.upper(), jnp.float32)]
    assert routed == ["conv1"]
    kinds = {(m.k1, m.k2) for name, m in convs.items() if name != "conv1"}
    assert kinds == {(1, 1), (3, 3), (5, 5)}
    conv1 = ((224, 224, 3), (7, 7, 3, 64), 2, "SAME")
    assert dense_stem(*conv1, jnp.float32)
    assert not dense_stem(*conv1, jnp.bfloat16)
    assert not dense_stem(*conv1, jnp.int8)
    spec = LayoutSpec(kind="toeplitz", h=224, w=224, c=3, k1=7, k2=7,
                      stride=2, padding="SAME")
    assert not dense_stem(*conv1, jnp.float32, spec)
    assert dense_stem(*conv1, jnp.float32, LayoutSpec(kind="nhwc"))
    # The kernel holds the whole padded image, folded, in VMEM: at 1,400
    # pixels a side that is more than the chip has.
    assert dense_stem((1000, 1000, 3), (7, 7, 3, 64), 2, "SAME", jnp.float32)
    assert not dense_stem((1400, 1400, 3), (7, 7, 3, 64), 2, "SAME",
                          jnp.float32)
    # The chunked im2col cases above reach the chunked kernel.
    for h, w_, ci, co, k1, k2, s, pad in CHUNKED:
        assert not dense_stem((h, w_, ci), (k1, k2, ci, co), s, pad,
                              jnp.float32)
    # The wrapper branches on the same predicate: conv1 lowers to the
    # named stem kernel, a 5x5 Cin-16 conv to the chunked one.
    x1, w1 = (jax.ShapeDtypeStruct(s, jnp.float32)
              for s in ((224, 224, 3), (7, 7, 3, 64)))
    x5, w5 = (jax.ShapeDtypeStruct(s, jnp.float32)
              for s in ((28, 28, 16), (5, 5, 16, 32)))
    conv1 = str(jax.make_jaxpr(lambda x, w: conv_im2col(
        x, w, stride=2, interpret=True))(x1, w1))
    conv5 = str(jax.make_jaxpr(lambda x, w: conv_im2col(
        x, w, interpret=True))(x5, w5))
    assert "conv_stem_dense" in conv1 and "conv_stem_dense" not in conv5


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
