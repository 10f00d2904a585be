"""``mamba1_lm.train_flops`` and ``kernel_cost_mamba1`` against counts made
from shapes at the cell's sizes."""

import json
import os

import pytest

from horovod_tpu.models import transformer as tfm
from perfbench import kernel_cost, kernel_cost_mamba1, run
from perfbench.adapters import mamba1_lm
from perfbench.peaks import peak, peaks_for

CONFIG = os.path.join(run.HERE, "configs", "ai21-jamba2-3b.json")


def _config():
    with open(CONFIG) as f:
        return json.load(f)


def test_mamba1_lm_train_flops_by_hand():
    config = _config()
    # Mamba-1: W_in 2560 x 10240, W_x 5120 x 192, W_dt 160 x 5120, W_out
    # 5120 x 2560.
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    assert mamba == 41_123_840
    # Attention: Wq, Wo 2560 x 2560; Wk, Wv 2560 x 128.
    attention = 2 * 2560 * 2560 + 2 * 2560 * 128
    assert attention == 13_762_560
    mlp, head = 3 * 2560 * 8192, 2560 * 16384
    weights = 13 * mamba + attention + 14 * mlp + head
    assert weights == 1_471_119_360
    tokens = 16384
    scan = 13 * tokens * 5120 * 12 * 16
    by_hand = 6 * weights * tokens + 6 * 16384 ** 2 * 2560 + scan
    assert mamba1_lm.train_flops(config, 16384, 1) == by_hand
    assert 148.9e12 < by_hand < 149.1e12
    # Two sequences: everything doubles (attention is per sequence).
    assert mamba1_lm.train_flops(config, 16384, 2) == 2 * by_hand


def test_matmul_parameters_are_the_models_matrices():
    """Against the program's own parameter tree at the rehearsal size:
    every leaf with two axes but the embedding, the convolution and
    ``A_log``."""
    config = run._load(CONFIG, rehearse=True)
    params = tfm.init_abstract(mamba1_lm.model_config(config, 256))
    counted = mamba1_lm.matmul_parameters(config)
    mlp = ("w_gate", "w_up", "w_down")

    def matrices(layer, skip=("mamba_conv", "mamba_a_log") + mlp):
        return sum(leaf.size for name, leaf in layer.items()
                   if leaf.ndim == 2 and name not in skip)

    mamba, attention = params["layers"][0], params["layers"][2]
    assert matrices(mamba) == counted["mamba"]
    assert matrices(attention) == counted["full_attention"]
    assert sum(mamba[name].size for name in mlp) == counted["mlp"]
    assert params["embed"].size == counted["head"]


@pytest.mark.parametrize("recompute", (False, True))
def test_selective_scan_cost_by_hand(recompute):
    cost = kernel_cost_mamba1.selective_scan_train(
        16384, 5120, 16, 13, recompute=recompute)
    assert cost["flops"] == 16384 * 13 * 5120 * 3 * 4 * 16
    # x in bf16, delta and y in float32, B and C of 16 in float32.
    forward = 5120 * 2 + 5120 * 4 + 2 * 16 * 4 + 5120 * 4
    backward = forward + 5120 * 2 + 5120 * 4 + 2 * 16 * 4
    moved = (2 if recompute else 1) * forward + backward
    assert cost["bytes"] == 16384 * 13 * moved
    # The bytes set the bound on a v5e, by a factor of forty.
    peaks = peaks_for("TPU v5 lite")
    ideal, bound = kernel_cost.roofline_seconds(
        cost, peak(peaks, "bf16_flops_per_s"), peak(peaks, "hbm_bytes_per_s"))
    assert bound == "memory"
    assert ideal == cost["bytes"] / peak(peaks, "hbm_bytes_per_s")
    if recompute:
        assert 0.045 < ideal < 0.050
