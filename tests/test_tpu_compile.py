"""Ahead-of-time compiles of the overlay kernels for a described TPU v5e.

No chip is needed: the TPU compiler builds each kernel for a v5e that is
described, not attached, and refuses what the chip's compiler would refuse
(unaligned blocks, unsupported primitives, too much VMEM) — faults that
interpret mode on the CPU cannot show. Shapes are the published layers of
GoogleNet (224) and Inception-v4 (299). The topology is described inside a
fixture, never at import, so every pytest worker collects the same tests and
only the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.cost_model import V5E, Dataflow
from repro.core.dse import candidate_shapes
from repro.core.layouts import LayoutSpec
from repro.kernels.conv_im2col.ops import conv_im2col
from repro.kernels.gemm.ops import batched_gemm, gemm, toeplitz_gemm
from repro.kernels.kn2row.ops import conv_kn2row
from repro.kernels.winograd.ops import conv_winograd

F32, I8 = jnp.float32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_text(one_chip):
    """Compile ``fn`` at the given (shape, dtype)s for one described chip
    and return the compiled program's text. The persistent compilation
    cache is off meanwhile: without a chip its entries cannot be read
    back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *args):
        shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                  for s, d in args]
        return jax.jit(fn).lower(*shapes).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _winograd_spec():
    # Inception-v4 A 3x3 input (35x35x64) stored as F(4,3) input tiles.
    return LayoutSpec(kind="winograd", h=35, w=35, c=64, k1=3, k2=3,
                      m=4, r=3)


def _toeplitz_spec():
    # GoogleNet inception-3a 3x3 (28x28, 96 -> 128) stored as its Toeplitz
    # matrix: the matched-layout consumer runs a plain GEMM on it.
    return LayoutSpec(kind="toeplitz", h=28, w=28, c=96, k1=3, k2=3,
                      stride=1, padding="SAME")


CASES = {
    "gemm_f32": (
        lambda a, b: gemm(a, b, interpret=False),
        ((784, 832), F32), ((832, 384), F32)),
    "gemm_int8_scale_out_scale": (
        lambda a, b, s: gemm(a, b, interpret=False, scale=s,
                             out_scale=0.05, epilogue="bias_relu",
                             bias=jnp.zeros((384,))),
        ((784, 832), I8), ((832, 384), I8), ((384,), F32)),
    "batched_gemm_winograd_f43": (
        lambda a, b: batched_gemm(a, b, interpret=False),
        ((36, 144, 64), F32), ((36, 64, 96), F32)),
    "toeplitz_gemm": (
        lambda t, w: toeplitz_gemm(t, w, _toeplitz_spec(), interpret=False),
        ((784, 864), F32), ((864, 128), F32)),
    "im2col_googlenet_conv1_7x7s2": (
        lambda x, w: conv_im2col(x, w, stride=2, interpret=False,
                                 epilogue="bias_relu",
                                 bias=jnp.zeros((64,))),
        ((8, 224, 224, 3), F32), ((7, 7, 3, 64), F32)),
    "im2col_googlenet_3a_3x3": (
        lambda x, w: conv_im2col(x, w, interpret=False, epilogue="relu"),
        ((8, 28, 28, 96), F32), ((3, 3, 96, 128), F32)),
    "kn2row_inception_v4_reduction_3x3s2": (
        lambda x, w: conv_kn2row(x, w, stride=2, padding="VALID",
                                 interpret=False, epilogue="relu"),
        ((35, 35, 384), F32), ((3, 3, 384, 384), F32)),
    "winograd_f43_inception_v4_a_3x3": (
        lambda x, w: conv_winograd(x, w, m=4, interpret=False,
                                   epilogue="bias_relu",
                                   bias=jnp.zeros((96,))),
        ((8, 35, 35, 64), F32), ((3, 3, 64, 96), F32)),
    "winograd_f43_from_stored_tiles": (
        lambda t, w: conv_winograd(t, w, m=4, interpret=False,
                                   in_layout=_winograd_spec()),
        ((81, 6, 6, 64), F32), ((3, 3, 64, 96), F32)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(compile_text, case):
    fn, *args = CASES[case]
    assert "tpu_custom_call" in compile_text(fn, *args)


@pytest.mark.parametrize("dataflow", list(Dataflow))
def test_largest_admitted_gemm_block_compiles(compile_text, dataflow):
    """The DSE's VMEM budget and the kernels' VMEM limit agree: the
    largest (p1, p2) ``candidate_shapes`` admits compiles under every
    dataflow's block binding."""
    p1, p2 = max(candidate_shapes(V5E), key=lambda s: s[0] * s[1])
    n = max(p1, p2)                 # every GEMM dim fills its block
    text = compile_text(
        lambda a, b: gemm(a, b, dataflow, p1, p2, interpret=False),
        ((n, n), F32), ((n, n), F32))
    assert "tpu_custom_call" in text
