"""The DYNAMAP Computing Unit overlay — single entry point for every conv.

The paper's §3 overlay is one GEMM engine reused by all layers; per layer
only the *algorithm wrapper* (im2col / kn2row / Winograd) and the *dataflow
binding* of the (P_SA1, P_SA2) array dims change. ``apply_conv`` is that
unit in software: it takes the plan's per-layer ``(algo, dataflow, p1, p2)``
and routes the convolution through the dataflow-bound GEMM blocks in
``kernels/gemm`` (Pallas path) or the pure-jnp oracles (reference path).

Layout semantics (§3.3, Table 2): ``in_layout``/``out_layout`` carry the
plan's DRAM store formats. A matched ``in_layout`` means ``x`` arrives in
the layer's own input layout (its Toeplitz matrix, or its scattered
Winograd tiles) — the matched streaming load, no re-gather; a non-NHWC
``out_layout`` makes the call emit its consumer's store format (the
store-side conversion fused into the producing layer). Backends that
cannot consume a layout directly (``lax``; mismatched specs) restore to
NHWC first — the converting load — so every (backend, layout) combination
computes the same function.

Batching semantics: every path accepts a single sample or a batch with one
leading dim and returns the matching rank; the un-batched rank follows the
layout (NHWC 3, Toeplitz 2, Winograd tiles 4). The Pallas kernels batch
through ``pallas_call``'s batching rule (an outer grid dimension), so the
compiled overlay program serves batched traffic without Python dispatch.

``compile_plan`` (executor.py) closes over these per-layer bindings at trace
time; tests monkeypatch this module's ``apply_conv`` to observe exactly
which (algorithm, dataflow, layouts) each layer was lowered with — wrap a
plain NHWC oracle with ``nhwc_conv`` so it honors the layout contract.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.core.algorithms import Algorithm, AlgoFamily
from repro.core.cost_model import Dataflow
from repro.core.layouts import LayoutSpec, is_nhwc
from repro.kernels.common import (PRECISIONS, apply_epilogue, dequantize,
                                  quantize, requantize, weight_scales)
from repro.kernels.conv_im2col.ops import conv_im2col
from repro.kernels.conv_im2col.ref import (conv_from_toeplitz_ref, conv_ref,
                                           conv_via_toeplitz_ref)
from repro.kernels.kn2row.ops import conv_kn2row
from repro.kernels.kn2row.ref import kn2row_ref
from repro.kernels.layouts import materialize, restore
from repro.kernels.winograd.ops import conv_winograd
from repro.kernels.winograd.ref import winograd_from_tiles_ref, winograd_ref


def nhwc_conv(fn):
    """Adapt a plain NHWC conv ``fn(x, w, ...)`` to the overlay's
    layout-carrying call contract: restore a non-NHWC input, materialize a
    requested output format. Reference executors (and tests that
    monkeypatch ``apply_conv`` with an oracle) wrap with this so a
    layout-aware compiled plan can still be replayed against them."""
    @functools.wraps(fn)
    def wrapper(x, w, *args, in_layout=None, out_layout=None, **kw):
        y = fn(restore(x, in_layout), w, *args, **kw)
        return materialize(y, out_layout)
    return wrapper


def apply_conv(x: jax.Array, w: jax.Array, algo: Algorithm,
               dataflow: Dataflow = Dataflow.NS,
               p1: int = 128, p2: int = 128, *,
               stride: int = 1, padding: str = "SAME",
               use_pallas: bool = False,
               backend: Optional[str] = None,
               interpret: Optional[bool] = None,
               epilogue: str = "none",
               bias: Optional[jax.Array] = None,
               in_layout: Optional[LayoutSpec] = None,
               out_layout: Optional[LayoutSpec] = None,
               precision: str = "bf16",
               in_scale: Optional[float] = None,
               out_scale: Optional[float] = None,
               in_quantized: bool = False) -> jax.Array:
    """Run one conv layer on the overlay under a plan binding.

    x: the layer input in ``in_layout`` (default NHWC): (H, W, Cin) /
    (B, H, W, Cin), a Toeplitz matrix (O1O2, K1K2·Cin), or Winograd tiles
    (tiles, T, T, Cin); w: (K1, K2, Cin, Cout). ``dataflow``/(p1, p2)
    select the Eq. 9 GEMM block binding — they only shape the Pallas
    execution schedule, never the math, so any binding produces identical
    outputs (the §3 invariant the tests assert); the same holds for every
    layout combination.

    ``backend`` (when given) overrides ``use_pallas``: "pallas" runs the
    Pallas kernels, "reference" the per-algorithm jnp oracles, and "lax"
    XLA's native spatial convolution — the "tiny convs via jnp" leg of a
    mixed-backend plan, and the strongest conv this host's XLA can emit
    (the autotuner measures it against the overlay algorithms per layer).

    ``epilogue`` ("none" | "relu" | "bias" | "bias_relu") streams the conv
    output through the §3 in-pipeline auxiliary units: on the Pallas path it
    fuses into the kernel's output flush (no DRAM round trip); the jnp
    reference/lax paths apply it post-hoc (XLA fuses it there) so every
    backend computes the same function — CONV+ReLU is ONE overlay call
    either way.

    ``precision`` ("bf16" | "int8") selects the quantized overlay path:
    int8 layers quantize their weights per-output-channel in-trace and
    their input per-tensor at the calibrated static ``in_scale`` (skipped
    when ``in_quantized`` says the producer already emitted int8 at this
    layer's scale — the fused precision edge), accumulate in int32, and
    fuse dequant+bias+relu(+``out_scale`` requant) into the kernel flush.
    Winograd layers reject int8 (the transforms amplify quantization
    error; the mapper never assigns it). Non-Pallas backends emulate int8
    with fake-quantized f32 operands — same quantization error, so the
    accuracy gate can measure on any backend.
    """
    in_layout = None if is_nhwc(in_layout) else in_layout
    out_layout = None if is_nhwc(out_layout) else out_layout
    if backend is not None and backend not in ("lax", "pallas", "reference"):
        raise ValueError(f"unknown backend {backend!r}")
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; want {PRECISIONS}")
    quant_kw = {}
    post_requant = None
    if precision == "int8":
        if algo.family is AlgoFamily.WINOGRAD:
            raise ValueError("Winograd is bf16-only: its input/output "
                             "transforms amplify quantization error")
        if in_scale is None:
            raise ValueError("int8 precision needs a calibrated in_scale")
        w_scale = weight_scales(w)
        use_p = use_pallas if backend is None else backend == "pallas"
        if use_p:
            # True int8 kernels: quantized operands, int32 accumulation,
            # dequant/requant fused into the epilogue flush. NHWC and
            # Toeplitz inputs hold raw activations, so quantization
            # commutes with the layout; anything else (e.g. a Winograd
            # store format holding transformed tiles) restores first.
            if not in_quantized:
                if in_layout is not None and in_layout.kind != "toeplitz":
                    x, in_layout = restore(x, in_layout), None
                x = quantize(x, in_scale)
            w = quantize(w, w_scale)
            quant_kw = dict(scale=in_scale * w_scale, out_scale=out_scale)
        else:
            # Fake-quant emulation (lax / reference): dequantized f32
            # operands carry the identical quantization error.
            if in_quantized:
                x = dequantize(x, in_scale)
            else:
                if in_layout is not None and in_layout.kind != "toeplitz":
                    x, in_layout = restore(x, in_layout), None
                x = dequantize(quantize(x, in_scale), in_scale)
            w = dequantize(quantize(w, w_scale), w_scale)
            post_requant = out_scale
    if backend == "lax":
        # XLA's conv wants spatial NHWC: converting load + store.
        y = apply_epilogue(
            conv_ref(restore(x, in_layout), w,
                     stride=stride, padding=padding),
            epilogue, bias)
        y = materialize(y, out_layout)
        return requantize(y, post_requant) if post_requant else y
    if backend is not None:
        use_pallas = backend == "pallas"
    fam = algo.family
    if fam is AlgoFamily.IM2COL:
        if use_pallas:
            return conv_im2col(x, w, stride=stride, padding=padding,
                               dataflow=dataflow, p1=p1, p2=p2,
                               interpret=interpret,
                               epilogue=epilogue, bias=bias,
                               in_layout=in_layout, out_layout=out_layout,
                               **quant_kw)
        if in_layout is not None and in_layout.kind == "toeplitz":
            y = apply_epilogue(
                conv_from_toeplitz_ref(x, w, in_layout.o1, in_layout.o2),
                epilogue, bias)
            y = materialize(y, out_layout)
            return requantize(y, post_requant) if post_requant else y
        y = apply_epilogue(
            conv_via_toeplitz_ref(restore(x, in_layout), w,
                                  stride=stride, padding=padding),
            epilogue, bias)
        y = materialize(y, out_layout)
        return requantize(y, post_requant) if post_requant else y
    if fam is AlgoFamily.KN2ROW:
        if use_pallas:
            return conv_kn2row(x, w, stride=stride, padding=padding,
                               dataflow=dataflow, p1=p1, p2=p2,
                               interpret=interpret,
                               epilogue=epilogue, bias=bias,
                               in_layout=in_layout, out_layout=out_layout,
                               **quant_kw)
        y = apply_epilogue(
            kn2row_ref(restore(x, in_layout), w,
                       stride=stride, padding=padding), epilogue, bias)
        y = materialize(y, out_layout)
        return requantize(y, post_requant) if post_requant else y
    # Winograd — stride-1 square kernels only (menu_for guarantees this);
    # non-square/strided layers never receive a Winograd assignment.
    assert stride == 1 and w.shape[0] == w.shape[1]
    if use_pallas:
        return conv_winograd(x, w, m=algo.m, padding=padding,
                             dataflow=dataflow, p1=p1, p2=p2,
                             interpret=interpret,
                             epilogue=epilogue, bias=bias,
                             in_layout=in_layout, out_layout=out_layout)
    if in_layout is not None and in_layout.kind == "winograd" \
            and in_layout.m == algo.m and w.shape[0] == in_layout.r:
        spec = in_layout
        tiles_conv = functools.partial(
            winograd_from_tiles_ref, w=w, m=algo.m, tiles_y=spec.tiles_y,
            tiles_x=spec.tiles_x, o1=spec.o1, o2=spec.o2)
        y = jax.vmap(tiles_conv)(x) if x.ndim == 5 else tiles_conv(x)
        return materialize(apply_epilogue(y, epilogue, bias), out_layout)
    x = restore(x, in_layout)
    if w.shape[0] == 3:
        y = apply_epilogue(winograd_ref(x, w, m=algo.m, padding=padding),
                           epilogue, bias)
        return materialize(y, out_layout)
    # K>r multi-round path has no standalone jnp ref; it runs the Pallas
    # implementation (still winograd math).
    return conv_winograd(x, w, m=algo.m, padding=padding,
                         dataflow=dataflow, p1=p1, p2=p2, interpret=interpret,
                         epilogue=epilogue, bias=bias,
                         out_layout=out_layout)
