"""``moe_lm.train_flops`` and ``kernel_cost_moe`` against counts made by
hand at the cell's shapes."""

import json
import os

import pytest

from perfbench import kernel_cost, kernel_cost_moe, run
from perfbench.adapters import moe_lm
from perfbench.peaks import peak, peaks_for


def _config():
    path = os.path.join(run.HERE, "configs", "olmoe-1b-7b-0125.json")
    with open(path) as f:
        return json.load(f)


def test_moe_lm_train_flops_by_hand():
    config = _config()
    assert config["num_hidden_layers"] == 2
    # Active per layer: q, k, v, o (4 d^2), 8 experts of 3 matrices d x f,
    # the router d x 64; the untied head once.
    layer = 4 * 2048 ** 2 + 8 * 3 * 2048 * 1024 + 2048 * 64
    assert layer == 67_239_936
    active = 2 * layer + 2048 * 50304
    tokens = 2 * 4096
    by_hand = 6 * active * tokens + 6 * 2 * 4096 ** 2 * 2048 * 2
    assert moe_lm.train_flops(config, 4096, 2) == by_hand
    assert by_hand / 1e12 == pytest.approx(12.5, abs=0.05)
    # Shares of the matmul FLOPs at depth 2: experts 42%, head 43%,
    # attention projections 14%.
    assert 2 * 8 * 3 * 2048 * 1024 / active == pytest.approx(0.424, abs=1e-3)
    assert 2048 * 50304 / active == pytest.approx(0.434, abs=1e-3)
    # All 64 experts' parameters are held, 8 are used.
    held = 2 * (layer + 56 * 3 * 2048 * 1024) + 2 * 2048 * 50304
    assert held / 1e9 == pytest.approx(1.045, abs=1e-3)


def test_expert_matmul_cost_by_hand():
    rows = 2 * 4096 * 8
    cost = kernel_cost_moe.expert_matmuls_train(rows, 2048, 1024, 64, 2)
    # 3 matmuls x (forward, gradient of rows, gradient of weights).
    assert cost["flops"] == 2 * 9 * 2 * rows * 2048 * 1024
    assert cost["flops"] / 1e12 == pytest.approx(4.95, abs=0.01)
    weights = 64 * 3 * 2048 * 1024
    assert weights == 402_653_184
    by_hand_bytes = 2 * 2 * (3 * weights + rows * (5 * 2048 + 4 * 1024))
    assert cost["bytes"] == by_hand_bytes
    # The split of the rows over the experts changes nothing.
    peaks = peaks_for("TPU v5 lite")
    seconds, bound = kernel_cost.roofline_seconds(
        cost, peak(peaks, "bf16_flops_per_s"), peak(peaks, "hbm_bytes_per_s"))
    assert bound == "compute"
    assert seconds * 1e3 == pytest.approx(25.1, abs=0.1)
    # With few rows an expert it is the weights' traffic that binds.
    few = kernel_cost_moe.expert_matmuls_train(64 * 8, 2048, 1024, 64, 2)
    assert kernel_cost.roofline_seconds(
        few, peak(peaks, "bf16_flops_per_s"),
        peak(peaks, "hbm_bytes_per_s"))[1] == "memory"
