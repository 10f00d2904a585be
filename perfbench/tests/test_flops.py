"""The yardstick's operation counts against counts made by hand."""

import json
import os

import pytest

from perfbench import kernel_cost, run
from perfbench.adapters import lm, resnet
from perfbench.peaks import peak, peaks_for


def _config(name):
    with open(os.path.join(run.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_lm_train_flops_by_hand():
    config = _config("cerebras-gpt-6.7b")
    # 6 layers of 4 d^2 (q, k, v, o) + 2 d f (MLP), plus the tied head.
    n = 6 * (4 * 4096 ** 2 + 2 * 4096 * 16384) + 4096 * 50257
    assert n == 1_413_812_224
    tokens = 4 * 2048
    by_hand = 6 * n * tokens + 6 * 4 * 2048 ** 2 * 4096 * 6
    assert lm.train_flops(config, 2048, 4) == by_hand
    assert by_hand / 1e12 == pytest.approx(72.0, abs=0.05)
    # The same tokens as one sequence of 8192: four times the attention.
    longer = lm.train_flops(config, 8192, 1)
    assert longer - by_hand == pytest.approx(
        3 * 6 * 4 * 2048 ** 2 * 4096 * 6)
    assert longer / 1e12 == pytest.approx(79.4, abs=0.05)


def test_resnet50_macs_by_hand():
    config = _config("resnet50-v1.5")
    stem = 112 * 112 * 49 * 3 * 64
    # Stage 1 at 56x56: first block 64->64->64->256 with a projection,
    # two more blocks 256->64->64->256.
    s1_first = 56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    s1_rest = 56 * 56 * (256 * 64 + 9 * 64 * 64 + 64 * 256)
    head = 2048 * 1000
    only_stage1 = dict(config, stage_sizes=[3], num_classes=0)
    assert resnet.forward_macs(only_stage1) == stem + s1_first + 2 * s1_rest
    macs = resnet.forward_macs(config)
    # The published count for ResNet-50 at 224x224 is 4.09 G
    # multiply-accumulates (v1.5's stride on the 3x3 adds ~0.2 G to
    # v1's 3.86 G).
    assert macs / 1e9 == pytest.approx(4.09, abs=0.01)
    assert macs - resnet.forward_macs(dict(config, num_classes=0)) == head
    assert resnet.train_flops(config, 256) == 6 * macs * 256
    assert 6 * macs / 1e9 == pytest.approx(24.5, abs=0.1)


def test_causal_attention_cost_by_hand():
    # One head, 4 positions, head dim 2: 10 causal score elements, each
    # 2 * 2 FLOPs per matmul term; 2 terms forward, 5 backward.
    cost = kernel_cost.causal_attention_train(1, 1, 4, 2)
    assert cost["flops"] == 10 * 4 * (2 + 5)
    # 12 passes over a [4, 2] bf16 tensor and the row statistics twice.
    assert cost["bytes"] == 12 * 4 * 2 * 2 + 2 * (2 * 4 * 4)
    seconds, bound = kernel_cost.roofline_seconds(
        {"flops": 197e12, "bytes": 819e9 / 2}, 197e12, 819e9)
    assert (seconds, bound) == (1.0, "compute")


def test_peaks_table():
    v5e = peaks_for("TPU v5 lite")
    assert peak(v5e, "bf16_flops_per_s") == 197e12
    assert peak(v5e, "hbm_bytes_per_s") == 819e9
    assert peaks_for("TPU v5e") == v5e
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peak(peaks_for("TPU v4"), "hbm_bytes_per_s")
