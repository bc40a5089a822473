"""FLOP and byte counts against layers worked out by hand."""
import pytest

from bench import counts, harness
from repro.cnn.models import googlenet, inception_v4
from repro.core.graph import ConvMeta

V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_googlenet_conv1_by_hand():
    # 7x7/2, 3 -> 64 channels, 224 -> 112.
    m = ConvMeta(c_in=3, c_out=64, h1=224, h2=224, k1=7, k2=7, stride=2)
    assert counts.conv_flops(m, 1) == 2 * 7 * 7 * 3 * 64 * 112 * 112
    assert counts.conv_flops(m, 1) == 236_027_904
    # input 224*224*3, weights 7*7*3*64 + 64 bias, output 112*112*64.
    assert counts.conv_bytes(m, 1, 4) == 4 * (150_528 + 9_408 + 64 + 802_816)
    # 3.85 MB at 819 GB/s is 4.70 us; 0.236 GFLOP at 197 TFLOP/s 1.20 us.
    t = counts.least_time_s(236_027_904, 3_851_264, V5E)
    assert t == pytest.approx(3_851_264 / 819e9)


def test_inception_1x1_by_hand():
    # inception_3a/1x1: 28x28, 192 -> 64, one tap.
    m = ConvMeta(c_in=192, c_out=64, h1=28, h2=28, k1=1, k2=1)
    assert counts.conv_flops(m, 8) == 2 * 192 * 64 * 28 * 28 * 8
    assert counts.conv_bytes(m, 8, 4) == 4 * (8 * 28 * 28 * 192
                                              + 192 * 64 + 64
                                              + 8 * 28 * 28 * 64)
    g = googlenet(224)
    node = next(n for n in g.nodes.values() if n.name == "inception_3a/1x1")
    assert node.conv == m


def test_flops_bound_when_compute_heavy():
    t = counts.least_time_s(197e12, 1.0, V5E)
    assert t == pytest.approx(1.0)


@pytest.mark.parametrize("name", ["googlenet-224", "inception_v4-299"])
def test_configs_match_the_graphs(name):
    config = harness.load_json(harness.BENCH / "configs" / f"{name}.json")
    build = {"googlenet": googlenet, "inception_v4": inception_v4}
    graph = build[config["builder"]](**config["args"])
    counts.check_totals(graph, config["expect"])
    assert counts.model_flops(graph) == 2 * config["expect"]["macs_per_image"]


def test_check_totals_refuses_another_graph():
    with pytest.raises(ValueError, match="convs"):
        counts.check_totals(googlenet(224, scale=0.5),
                            {"convs": 58, "params": 0, "macs_per_image": 0})


def test_googlenet_batch1_least_time():
    # Every conv layer alone, bound by the larger of its two times: the
    # network's convs need at least 70 us on a v5e at batch 1 in f32.
    t = counts.conv_least_time_s(googlenet(224), V5E)
    assert 60e-6 < t < 80e-6
