"""Operation and byte counts against hand counts at tiny shapes."""
import json
import os

import pytest

from bench import harness, work


def test_conv_and_linear_flops():
    # 2x2 map, 3 in, 5 out, 3x3 kernel: 4 positions x 9 x 3 x 5 MACs.
    assert work.conv_flops(2, 2, 3, 5, 3) == 2 * 4 * 9 * 3 * 5
    assert work.linear_flops(2, 3, 4) == 48


def test_event_matmul_work():
    # (4, 8) operand with 5 ones against (8, 3) weights: 5 x 3 synaptic
    # accumulations; 32 bits of spikes, 24 f32 weights, 12 f32 outputs.
    assert work.event_matmul_work(5, 4, 8, 3) == (30, 4 + 96 + 48)
    # an 8-bit operand counts 8 bits an element
    assert work.event_matmul_work(5, 4, 8, 3, operand_bits=8) == (30,
                                                                  32 + 144)


def test_lif_work():
    # (T=2, 4 rows, 8 lanes): 64 f32 reads, 64 bits of spikes; the maps
    # of one (128, 128) tile: 1 tile count + 16 chunk counts, int32.
    assert work.lif_work(2, 4, 8, maps=False) == 256 + 8
    assert work.lif_work(2, 4, 8, maps=True) == 256 + 8 + 4 * 17


def test_least_seconds_takes_the_larger_bound():
    assert work.least_seconds(10, 1, 10, 1) == 1
    assert work.least_seconds(100, 1, 10, 1) == 10


def _model(config):
    path = os.path.join(harness.BENCH, "configs", config)
    with open(path + ".json") as f:
        return json.load(f), harness.load_module(path + ".py")


def test_spikingformer_flops_by_hand():
    cfg, model = _model("spikingformer-4-256")
    cfg.update(depth=1, dim=16, n_heads=2, mlp_ratio=2,
               sps_channels=[2, 4, 8, 16], img=8, n_classes=3, t_steps=2)
    # stem at 8x8, 8x8 (pool), 4x4 (pool), 2x2 -> 4 tokens of width 16
    stem = (64 * 9 * 3 * 2 + 64 * 9 * 2 * 4 + 16 * 9 * 4 * 8
            + 4 * 9 * 8 * 16)
    block = 4 * 4 * 16 * 16 + 4 * 16 * 32 + 4 * 32 * 16
    assert model.dense_flops_per_image(cfg) == 2 * (2 * (stem + block)
                                                    + 16 * 3)


def test_vgg11_flops_by_hand():
    cfg, model = _model("vgg11")
    cfg.update(layers=[2, "M", 4, 4, "M"], img=8, n_classes=3, t_steps=2,
               fc_pool=2)
    # 8x8: 3->2; 4x4: 2->4, 4->4; classifier over (2/2)^2 x 4 inputs
    convs = 64 * 9 * 3 * 2 + 16 * 9 * 2 * 4 + 16 * 9 * 4 * 4
    assert model.dense_flops_per_image(cfg) == 2 * 2 * (convs + 4 * 3)


@pytest.mark.parametrize("config, gflop", [("spikingformer-4-256", 2.07),
                                           ("vgg11", 1.22)])
def test_published_sizes(config, gflop):
    cfg, model = _model(config)
    assert model.dense_flops_per_image(cfg) / 1e9 == pytest.approx(gflop,
                                                                   abs=0.01)
