"""Int8 quantized overlay path: primitives, kernels, the precision PBQP
dimension, the accuracy gate, and cross-precision cache/tuning keying."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cnn import overlay
from repro.cnn.executor import (ExecutableCache, compile_plan,
                                executable_cache_key, forward, init_params)
from repro.cnn.models import vgg16
from repro.core.algorithms import IM2COL, KN2ROW, Algorithm, AlgoFamily
from repro.core.cost_model import V5E, V5E_INT8
from repro.core.graph import ConvMeta, Graph, LayerKind
from repro.core.mapper import lower_plan, map_network
from repro.core.quant import (calibrate_act_scales, layer_errors,
                              plan_mixed_precision)
from repro.kernels.common import (INT8_MAX, apply_epilogue, dequantize,
                                  pad_bias, quantize, requantize,
                                  weight_scales)
from repro.kernels.conv_im2col.ops import conv_im2col
from repro.kernels.gemm.ops import gemm
from repro.kernels.kn2row.ops import conv_kn2row

WINOGRAD = Algorithm(AlgoFamily.WINOGRAD, m=2, r=3)


def chain_graph(h=8, c=8):
    """INPUT -> 3x3 CONV -> 1x1 CONV -> OUTPUT (one fusable conv edge)."""
    g = Graph()
    i = g.add_node(LayerKind.INPUT, out_shape=(h, h, 3))
    c1 = g.add_node(LayerKind.CONV, conv=ConvMeta(3, c, h, h, 3, 3))
    c2 = g.add_node(LayerKind.CONV, conv=ConvMeta(c, c, h, h, 1, 1))
    o = g.add_node(LayerKind.OUTPUT, out_shape=(h, h, c))
    g.chain([i, c1, c2, o])
    return g, c1, c2


def fake_quant(x, scale):
    return dequantize(quantize(x, scale), scale)


# ---------------------------------------------------------------------------
# Primitives: quantize/dequantize/requantize, weight_scales, pad_bias,
# apply_epilogue validation + requantize variants.
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_and_saturation():
    x = jnp.array([-3.0, -0.01, 0.0, 0.01, 2.0, 5.0])
    q = quantize(x, 2.0 / INT8_MAX)
    assert q.dtype == jnp.int8
    assert int(q[0]) == -INT8_MAX and int(q[-1]) == INT8_MAX  # saturate
    err = jnp.abs(dequantize(q, 2.0 / INT8_MAX) - jnp.clip(x, -2.0, 2.0))
    assert float(jnp.max(err)) <= 2.0 / INT8_MAX / 2 + 1e-7


def test_weight_scales_per_output_channel():
    w = jnp.stack([jnp.full((3, 3, 4), 0.5), jnp.full((3, 3, 4), 2.0)],
                  axis=-1)                                  # (3,3,4,2)
    s = weight_scales(w)
    assert s.shape == (2,)
    np.testing.assert_allclose(np.asarray(s),
                               [0.5 / INT8_MAX, 2.0 / INT8_MAX])
    # All-zero channels get the epsilon floor, never a 0 divisor.
    assert float(weight_scales(jnp.zeros((1, 1, 1, 1)))[0]) > 0


def test_pad_bias_shapes_and_validation():
    b = jnp.arange(3.0)
    padded = pad_bias(b, 3, 8)
    assert padded.shape == (1, 8)
    np.testing.assert_allclose(np.asarray(padded)[0, :3], np.asarray(b))
    np.testing.assert_allclose(np.asarray(padded)[0, 3:], 0.0)
    assert pad_bias(None, 3, 8) is None
    with pytest.raises(AssertionError):
        pad_bias(jnp.zeros((4,)), 3, 8)                     # shape mismatch


def test_apply_epilogue_validation():
    y = jnp.ones((2, 2))
    with pytest.raises(ValueError, match="unknown epilogue"):
        apply_epilogue(y, "gelu")
    with pytest.raises(ValueError, match="needs a bias"):
        apply_epilogue(y, "bias")
    with pytest.raises(ValueError, match="needs a bias"):
        apply_epilogue(y, "bias_relu")


def test_apply_epilogue_requantize_variants():
    acc = jnp.array([[-200, 50], [400, -10]], jnp.int32)
    scale = jnp.array([[0.01, 0.02]])
    bias = jnp.array([0.5, -0.5])
    out_scale = 0.05
    got = apply_epilogue(acc, "bias_relu", bias, scale=scale,
                         out_scale=out_scale)
    want = requantize(jnp.maximum(acc * scale + bias, 0), out_scale)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # Without out_scale the flush stays f32; scale dequantizes first.
    f32 = apply_epilogue(acc, "relu", scale=scale)
    assert f32.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(f32),
                               np.maximum(np.asarray(acc) * [[0.01, 0.02]], 0))


# ---------------------------------------------------------------------------
# Int8 kernels vs the dequantized f32 reference.
# ---------------------------------------------------------------------------

def test_int8_gemm_matches_dequantized_reference():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(k1, (16, 32), jnp.float32)
    b = jax.random.normal(k2, (32, 24), jnp.float32)
    in_scale = float(jnp.max(jnp.abs(a))) / INT8_MAX
    w_scale = weight_scales(b)
    out = gemm(quantize(a, in_scale), quantize(b, w_scale),
               interpret=True, scale=in_scale * w_scale)
    ref = fake_quant(a, in_scale) @ fake_quant(b, w_scale)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # Requantized output is int8 at the requested scale.
    q = gemm(quantize(a, in_scale), quantize(b, w_scale), interpret=True,
             scale=in_scale * w_scale, out_scale=0.1)
    assert q.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(q),
                                  np.asarray(requantize(ref, 0.1)))


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("conv_fn", [conv_im2col, conv_kn2row],
                         ids=["im2col", "kn2row"])
def test_int8_conv_kernels_match_dequantized_reference(conv_fn, stride):
    kx, kw = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (6, 6, 8), jnp.float32)
    w = jax.random.normal(kw, (3, 3, 8, 16), jnp.float32)
    in_scale = float(jnp.max(jnp.abs(x))) / INT8_MAX
    w_scale = weight_scales(w)
    from repro.kernels.conv_im2col.ref import conv_ref
    ref = conv_ref(fake_quant(x, in_scale), fake_quant(w, w_scale),
                   stride=stride)
    out = conv_fn(quantize(x, in_scale), w=quantize(w, w_scale),
                  stride=stride, interpret=True, scale=in_scale * w_scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_overlay_int8_pallas_matches_emulation():
    """The true int8 kernels and the fake-quant emulation carry the same
    quantization error — the accuracy gate's measurement assumption."""
    kx, kw = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(kx, (6, 6, 8), jnp.float32)
    w = jax.random.normal(kw, (3, 3, 8, 8), jnp.float32)
    in_scale = float(jnp.max(jnp.abs(x))) / INT8_MAX
    for algo in (IM2COL, KN2ROW):
        kw_ = dict(precision="int8", in_scale=in_scale, epilogue="relu")
        got = overlay.apply_conv(x, w, algo, backend="pallas",
                                 interpret=True, **kw_)
        ref = overlay.apply_conv(x, w, algo, backend="lax", **kw_)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


def test_overlay_int8_rejects_winograd_and_missing_scale():
    x = jnp.zeros((6, 6, 4))
    w = jnp.zeros((3, 3, 4, 4))
    with pytest.raises(ValueError, match="bf16-only"):
        overlay.apply_conv(x, w, WINOGRAD, precision="int8", in_scale=0.1)
    with pytest.raises(ValueError, match="in_scale"):
        overlay.apply_conv(x, w, IM2COL, precision="int8")
    with pytest.raises(ValueError, match="unknown precision"):
        overlay.apply_conv(x, w, IM2COL, precision="fp8")


# ---------------------------------------------------------------------------
# Precision as a PBQP dimension + lowering.
# ---------------------------------------------------------------------------

def test_map_network_quantize_emits_precisions():
    g = vgg16(res=8, scale=0.05)
    plan = map_network(g, quantize=True)
    convs = [n.id for n in g.conv_nodes()]
    assert set(plan.precisions) == set(convs)
    assert any(p == "int8" for p in plan.precisions.values())
    # int8 layers must be priced cheaper than their bf16 twin would be:
    # the joint solve only picks int8 when it wins, and V5E_INT8 doubles
    # peak MACs, so the quantized plan can never cost more.
    bf16 = map_network(g)
    assert plan.total_cost_s <= bf16.total_cost_s + 1e-12
    assert not bf16.precisions                 # unquantized plan: empty map


def test_int8_cost_model_predicts_speedup():
    assert V5E_INT8.peak_flops >= 1.5 * V5E.peak_flops
    assert V5E_INT8.dtype_bytes < V5E.dtype_bytes


def test_force_bf16_pins_and_lowering_is_bitwise_stable():
    g = vgg16(res=8, scale=0.05)
    plan = map_network(g, quantize=True)
    int8_nodes = [n for n, p in plan.precisions.items() if p == "int8"]
    demote = int8_nodes[:1]
    pinned = map_network(g, quantize=True, force_bf16=demote)
    for nid in demote:
        assert pinned.precisions[nid] == "bf16"
    # A demoted layer's lowering is identical to the all-bf16 plan's —
    # force_bf16 removes its int8 entries entirely, so its choice vector
    # (and the solved binding) matches the unquantized build.
    all_bf16 = map_network(g)
    for nid in demote:
        assert pinned.assignment[nid] == all_bf16.assignment[nid]
        assert pinned.dataflows[nid] == all_bf16.dataflows[nid]


def test_lower_plan_int8_requires_scales_and_rejects_winograd():
    g, c1, c2 = chain_graph()
    plan = map_network(g)
    plan.precisions = {c1: "int8"}
    with pytest.raises(ValueError, match="act_scales"):
        lower_plan(g, plan)
    plan.assignment[c1] = WINOGRAD
    with pytest.raises(ValueError, match="bf16-only"):
        lower_plan(g, plan, act_scales={c1: 0.1})


def test_fused_precision_edge():
    """int8 -> int8 single-successor NHWC edge: the producer requantizes
    into the consumer's scale, the edge carries int8, and the consumer
    skips its own input quantization."""
    g, c1, c2 = chain_graph()
    plan = map_network(g)
    plan.assignment[c1] = plan.assignment[c2] = IM2COL
    plan.precisions = {c1: "int8", c2: "int8"}
    scales = {c1: 0.02, c2: 0.03}
    # elide=False keeps the edge NHWC — the only store format precision
    # fusion rides (an elided Toeplitz edge stays a per-layer quantize).
    prog = lower_plan(g, plan, act_scales=scales, elide=False)
    assert prog.convs[c1].out_scale == pytest.approx(0.03)
    assert prog.convs[c2].in_quantized
    assert prog.transitions[(c1, c2)].precision == "int8"
    assert (c1, c2) in prog.quantized_edges
    # The compiled fused-edge program still matches the f32 reference to
    # within quantization error.
    params = init_params(g, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 8, 3), jnp.float32)
    ref = forward(g, params, x)
    run = compile_plan(g, plan, use_pallas=True, interpret=True,
                       act_scales=scales, elide=False)
    got = run(params, x)
    rel = float(jnp.max(jnp.abs(got - ref)) / (jnp.max(jnp.abs(ref)) + 1e-12))
    assert rel < 0.1
    # An elided (Toeplitz) edge never fuses precision: each layer
    # quantizes its own input.
    elided = lower_plan(g, plan, act_scales=scales)
    assert elided.convs[c1].out_scale is None
    assert not elided.quantized_edges
    # Demoting the consumer breaks the fusion: the boundary reverts to a
    # plain f32 edge with no requantized producer output.
    plan.precisions = {c1: "int8", c2: "bf16"}
    prog2 = lower_plan(g, plan, act_scales=scales, elide=False)
    assert prog2.convs[c1].out_scale is None
    assert not prog2.quantized_edges


# ---------------------------------------------------------------------------
# Calibration + the accuracy gate.
# ---------------------------------------------------------------------------

def test_calibrate_act_scales_covers_all_convs():
    g = vgg16(res=8, scale=0.05)
    params = init_params(g, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 3), jnp.float32)
    scales = calibrate_act_scales(g, params, x)
    assert set(scales) == {n.id for n in g.conv_nodes()}
    assert all(s > 0 for s in scales.values())
    # First conv sees the raw input: scale = amax(x) / 127 exactly.
    first = min(scales)
    assert scales[first] == pytest.approx(
        float(jnp.max(jnp.abs(x))) / INT8_MAX)


def test_gate_every_int8_layer_within_tolerance():
    g = vgg16(res=8, scale=0.05)
    params = init_params(g, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 3), jnp.float32)
    report = plan_mixed_precision(g, params, x, tol=0.05)
    int8 = [n for n, p in report.plan.precisions.items() if p == "int8"]
    assert int8, "gate demoted everything on a well-behaved network"
    for nid in int8:
        assert report.errors[nid] <= report.tol
    assert report.precision_mix["int8"] == len(int8)
    # The gated plan compiles and tracks the f32 reference.
    run = compile_plan(g, report.plan, use_pallas=True, interpret=True,
                       act_scales=report.act_scales)
    ref = forward(g, params, x)
    np.testing.assert_allclose(np.asarray(run(params, x)), np.asarray(ref),
                               rtol=0.1, atol=0.05)


def test_gate_demotes_sensitive_layer():
    """An activation-outlier input makes the first conv's per-tensor scale
    useless (everything else quantizes to ~0): the gate must demote it
    back to bf16, bitwise-identically to the bf16 plan."""
    g = vgg16(res=8, scale=0.05)
    params = init_params(g, jax.random.PRNGKey(0))
    x = np.array(jax.random.normal(jax.random.PRNGKey(1), (8, 8, 3)))
    x[0, 0, 0] = 1000.0                       # the deliberate outlier
    x = jnp.asarray(x)
    report = plan_mixed_precision(g, params, x, tol=0.05)
    first = min(n.id for n in g.conv_nodes())
    assert first in report.demoted
    assert report.plan.precisions[first] == "bf16"
    assert report.errors[first] > report.tol
    all_bf16 = map_network(g)
    assert report.plan.assignment[first] == all_bf16.assignment[first]
    assert report.plan.dataflows[first] == all_bf16.dataflows[first]


def test_layer_errors_isolated_and_small():
    g, c1, c2 = chain_graph()
    params = init_params(g, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 8, 3), jnp.float32)
    scales = calibrate_act_scales(g, params, x)
    errs = layer_errors(g, params, x, scales)
    assert set(errs) == {c1, c2}
    assert all(0 <= e < 0.05 for e in errs.values())


# ---------------------------------------------------------------------------
# Cross-precision executable cache + tuning keys.
# ---------------------------------------------------------------------------

def test_executable_cache_distinguishes_precision():
    g = vgg16(res=8, scale=0.05)
    params = init_params(g, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 3), jnp.float32)
    report = plan_mixed_precision(g, params, x, tol=0.05)
    bf16_plan = map_network(g)
    cache = ExecutableCache()
    common = dict(use_pallas=True, interpret=True, cache=cache)
    run_q = compile_plan(g, report.plan, act_scales=report.act_scales,
                         **common)
    run_b = compile_plan(g, bf16_plan, **common)
    assert cache.stats() == {"entries": 2, "hits": 0, "misses": 2}
    # Same (graph, plan, bucket, options) at each precision: exact hits.
    again_q = compile_plan(g, report.plan, act_scales=report.act_scales,
                           **common)
    again_b = compile_plan(g, bf16_plan, **common)
    assert again_q is run_q and again_b is run_b
    assert cache.stats() == {"entries": 2, "hits": 2, "misses": 2}
    # Recalibration alone must recompile (scales are baked into the trace).
    other = {n: s * 2 for n, s in report.act_scales.items()}
    compile_plan(g, report.plan, act_scales=other, **common)
    assert cache.stats() == {"entries": 3, "hits": 2, "misses": 3}
    k_q = executable_cache_key(g, report.plan, use_pallas=True,
                               interpret=True,
                               act_scales=report.act_scales)
    k_b = executable_cache_key(g, bf16_plan, use_pallas=True, interpret=True)
    assert k_q != k_b


def test_tuning_record_precision_keys():
    from repro.core.autotune import (Binding, LayerTuning, TuningRecord,
                                     parse_record_key, record_key)
    conv = ConvMeta(8, 8, 8, 8, 3, 3)
    kb = record_key(conv, 2)
    kq = record_key(conv, 2, "int8")
    assert kq == kb + "#int8" and kb != kq
    assert parse_record_key(kb)[2] == "bf16"
    assert parse_record_key(kq) == parse_record_key(kb)[:2] + ("int8",)
    b = Binding("im2col", "NS", 128, 128, "reference")
    rec = TuningRecord({kq: LayerTuning(binding=b, measured_s=1e-3,
                                        candidates=[], batch=2,
                                        precision="int8")})
    # No cross-precision fallback in either direction.
    assert rec.lookup(conv, 2, "int8") is not None
    assert rec.lookup(conv, 2) is None
    assert rec.buckets_for(conv, "int8") == [2]
    assert rec.buckets_for(conv) == []
    # JSON round trip preserves the precision tag.
    rec2 = TuningRecord.from_json(rec.to_json())
    assert rec2.entries[kq].precision == "int8"
    assert rec2.lookup(conv, 2, "int8").binding == b


def test_engine_stats_report_precision_mix():
    from repro.serving.cnn_engine import CNNRequest, CNNServingEngine
    g = vgg16(res=8, scale=0.05)
    params = init_params(g, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 3), jnp.float32)
    report = plan_mixed_precision(g, params, x, tol=0.05)
    eng = CNNServingEngine(g, params, report.plan, batch_size=2,
                           act_scales=report.act_scales)
    eng.submit(CNNRequest(rid=0, image=np.zeros((8, 8, 3), np.float32)))
    eng.run_until_done()
    mix = eng.stats()["precision"]
    assert mix["mix"] == report.precision_mix
    assert mix["calibrated"]
    assert mix["int8_layers"] == sorted(
        n for n, p in report.plan.precisions.items() if p == "int8")
    # A precision-free plan reports all-bf16, uncalibrated.
    eng2 = CNNServingEngine(g, params, map_network(g), batch_size=2)
    mix2 = eng2.stats()["precision"]
    assert mix2["mix"]["int8"] == 0 and not mix2["calibrated"]
