"""``bd_moe_lm.train_flops`` against a count made from shapes at the cell's
sizes, term by term, and the flash kernels' cost under the block-diffusion
mask."""

import json
import os

from horovod_tpu.models import transformer as tfm
from perfbench import kernel_cost, kernel_cost_bd, run
from perfbench.adapters import bd_moe_lm
from perfbench.peaks import peak, peaks_for

CONFIG = os.path.join(run.HERE, "configs", "sdar-30b-a3b-chat.json")
L, B = 8192, 4
# Pairs one head needs: L^2 + L b of the (2 L)^2 over both halves.
PAIRS = L * L + L * B


def _config():
    with open(CONFIG) as f:
        return json.load(f)


def test_needed_pairs_by_hand():
    assert PAIRS == 67_141_632
    assert kernel_cost_bd.needed_pairs(L, B) == PAIRS
    # Three blocks of 2: clean x clean 4 x 6, noised x clean 4 x 3,
    # noised x noised 4 x 3: 6^2 + 6 x 2.
    assert kernel_cost_bd.needed_pairs(6, 2) == 24 + 12 + 12 == 48
    # One block: the block-causal mask is no mask, the copy reads itself.
    assert kernel_cost_bd.needed_pairs(4, 4) == 16 + 0 + 16
    assert abs(PAIRS / (2 * L) ** 2 - 0.25) < 2e-4
    # Twice what a causal mask over L tokens needs.
    assert abs(PAIRS / (L * (L + 1) // 2) - 2.0) < 1e-3


def test_bd_moe_lm_train_flops_by_hand():
    config = _config()
    layers = config["num_hidden_layers"]
    # W_q 2048 x (32 x 128), W_k and W_v 2048 x (4 x 128), W_o 4096 x 2048.
    attention = 2 * 2048 * 4096 + 2 * 2048 * 512
    assert attention == 18_874_368
    # Router 2048 x 128 and 8 x 16 / 128 = 1 expert of 3 x 2048 x 768 a
    # position on this chip.
    experts = 2048 * 128 + 1.0 * 3 * 2048 * 768
    assert experts == 4_980_736
    head = 2048 * 18992
    # The layers over both halves, the head over the noised half alone.
    weights = 6 * (2 * L * layers * (attention + experts) + L * head)
    pairs = 12 * PAIRS * 32 * 128 * layers
    by_hand = weights + pairs
    assert bd_moe_lm.train_flops(config, L, 1) == by_hand
    assert layers == 13 and 75.2e12 < by_hand < 75.4e12
    # Attention over the needed pairs is 57% of it; a program that ran a
    # causal mask over the 16384 positions would compute twice that, one
    # that sent the clean half through the head 1.9 TFLOP more, and
    # neither is credited.
    assert 0.56 < pairs / by_hand < 0.58
    assert 1.9e12 < 6 * L * head < 2.0e12
    # Two sequences: everything doubles (attention is per sequence).
    assert bd_moe_lm.train_flops(config, L, 2) == 2 * by_hand


def test_matmul_parameters_are_the_models_matrices():
    """Against the program's own parameter tree at the rehearsal size:
    every leaf with two axes but the embedding, an expert at the share of
    it a position uses here."""
    config = run._load(CONFIG, rehearse=True)
    params = tfm.init_abstract(bd_moe_lm.model_config(config, 256))
    counted = bd_moe_lm.matmul_parameters(config)
    attention, experts = ("wq", "wk", "wv", "wo"), ("w_gate", "w_up",
                                                    "w_down")
    for layer in params["layers"]:
        assert sum(layer[n].size for n in attention) == counted["attention"]
        held = layer["w_up"].shape[0]
        a_position = (config["num_experts_per_tok"] * held
                      / config["published"]["num_experts"])
        assert (layer["router"].size + a_position * sum(
            layer[n].size for n in experts) / held == counted["experts"])
        others = [name for name, leaf in layer.items() if leaf.ndim >= 2
                  and name not in attention + experts + ("router",)]
        assert not others, others
    assert params["head"].size == counted["head"]


def test_kernel_cost_by_hand():
    """The flash kernels under the mask need what attention over the
    needed pairs needs, and the MXU bounds it on a v5e."""
    v5e = peaks_for("TPU v5 lite")
    flash = kernel_cost_bd.block_diffusion_attention_train(1, 32, L, B, 128)
    assert flash["flops"] == 32 * PAIRS * 7 * 2 * 128
    tensor = 32 * 2 * L * 128 * 2
    assert flash["bytes"] == 12 * tensor + 2 * 2 * 32 * 2 * L * 4
    seconds, bound = kernel_cost.roofline_seconds(
        flash, peak(v5e, "bf16_flops_per_s"), peak(v5e, "hbm_bytes_per_s"))
    # 19.5 ms a layer, twice gpt67_t8192's 9.8 ms for half as many pairs.
    assert bound == "compute" and 19.5e-3 < seconds < 19.6e-3
    causal = kernel_cost.causal_attention_train(1, 32, L, 128)
    assert abs(flash["flops"] / causal["flops"] - 2.0) < 1e-3
