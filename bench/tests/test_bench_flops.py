"""Operation counts of ``bench/flops.py`` against hand counts."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import flops, spec  # noqa: E402

MLP = spec.load_json(spec.config_path("mlp-mnist"))
THREESFC = spec.load_json(spec.traffic_path("3sfc-float-n10"))
# the repo's Table 2 ConvNet (models/cnn.make_convnet) on CIFAR-10 shapes,
# and signSGD at the paper's round, as a later cell would give them
CONVNET = {"input_shape": [32, 32, 3], "layers": [
    {"kind": "conv", "cin": 3, "cout": 32, "k": 3, "stride": 1},
    {"kind": "conv", "cin": 32, "cout": 64, "k": 3, "stride": 2},
    {"kind": "conv", "cin": 64, "cout": 128, "k": 3, "stride": 2},
    {"kind": "conv", "cin": 128, "cout": 256, "k": 3, "stride": 2},
    {"kind": "mean_pool"},
    {"kind": "dense", "in": 256, "out": 10}]}
SIGNSGD = dict(THREESFC, strategy="signsgd")


def test_mlp_forward_is_397600_per_sample():
    # 2 * (784*200 + 200*200 + 200*10)
    assert flops.layer_flops(MLP) == [313600, 80000, 4000]
    assert flops.forward_flops(MLP) == 397600
    assert flops.param_count(MLP) == 199210


def test_convnet_per_layer_convolutions():
    # 2 * H_out * W_out * 3 * 3 * C_in * C_out, SAME padding
    assert flops.layer_flops(CONVNET) == [
        2 * 32 * 32 * 9 * 3 * 32,        # 1,769,472
        2 * 16 * 16 * 9 * 32 * 64,       # 9,437,184
        2 * 8 * 8 * 9 * 64 * 128,        # 9,437,184
        2 * 4 * 4 * 9 * 128 * 256,       # 9,437,184
        2 * 256 * 10,                    # 5,120
    ]
    assert flops.param_count(CONVNET) == 390986


def test_round_counts_by_hand():
    d, f = 199210, 397600
    local = 3 * f * 5 * 32
    ev = 3 * f * 1 + 6 * d
    threesfc = 10 * 3 * ev + ev + 4 * d + d
    assert flops.encode_flops(MLP, THREESFC) == threesfc
    assert flops.round_flops(MLP, THREESFC) == 10 * (local + threesfc) + 11 * d
    assert flops.round_flops(MLP, SIGNSGD) == 10 * (local + 4 * d) + 11 * d
