"""``ssm_moe_lm.train_flops`` and ``kernel_cost_ssm`` against counts made
from shapes at the cell's sizes."""

import json
import os

import pytest

from horovod_tpu.models import transformer as tfm
from perfbench import kernel_cost, kernel_cost_ssm, run
from perfbench.adapters import ssm_moe_lm
from perfbench.peaks import peak, peaks_for

CONFIG = os.path.join(run.HERE, "configs", "nemotron-3-super-120b-a12b.json")


def _config():
    with open(CONFIG) as f:
        return json.load(f)


def test_ssm_moe_lm_train_flops_by_hand():
    config = _config()
    # Mamba-2: W_in 4096 x (8192 + 10240 + 128), W_out 8192 x 4096.
    mamba = 4096 * 18560 + 8192 * 4096
    assert mamba == 109_576_192
    # Attention: Wq, Wo 4096 x 4096; Wk, Wv 4096 x (2 x 128).
    attention = 2 * 4096 * 4096 + 2 * 4096 * 256
    assert attention == 35_651_584
    # Expert layer: router 4096 x 512, latent in and out 4096 x 1024,
    # shared 2 x 4096 x 5376, and 22 x 8 / 512 of an expert of 2 x 1024 x
    # 2688 a token on this chip.
    here = 22 * 8 / 512
    assert here == 0.34375
    experts = (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
               + here * 2 * 1024 * 2688)
    assert experts == 56_418_304
    head, combine = 4096 * 16384, 8192 * 4096
    weights = (5 * mamba + attention + 5 * experts + head
               + combine + attention + experts + head)
    assert weights == 1_125_466_112
    tokens = 8192
    by_hand = 6 * weights * tokens + 2 * 6 * 8192 ** 2 * 4096
    assert ssm_moe_lm.train_flops(config, 8192, 1) == by_hand
    assert 58.5e12 < by_hand < 58.7e12
    # Two sequences: everything doubles (attention is per sequence).
    assert ssm_moe_lm.train_flops(config, 8192, 2) == 2 * by_hand


def test_matmul_parameters_are_the_models_matrices():
    """Against the program's own parameter tree at the rehearsal size:
    every leaf with two axes but the embedding and the convolution, an
    expert at the share of it a token uses here."""
    config = run._load(CONFIG, rehearse=True)
    params = tfm.init_abstract(ssm_moe_lm.model_config(config, 256))
    counted = ssm_moe_lm.matmul_parameters(config)

    def matrices(layer, skip=("ssm_conv",)):
        return sum(leaf.size for name, leaf in layer.items()
                   if leaf.ndim == 2 and name not in skip)

    mamba, experts, attention = (params["layers"][i] for i in (0, 1, 7))
    assert matrices(mamba) == counted["mamba2"]
    assert matrices(attention) == counted["attention"]
    held = experts["w_up"].shape[0]
    routed = (experts["w_up"].size + experts["w_down"].size) / held
    a_token = config["num_experts_per_tok"] * held / config["router_width"]
    assert matrices(experts) + a_token * routed == counted["mlp"]
    assert params["head"].size == counted["head"]
    assert params["mtp"]["w_eh"].size == counted["mtp_combine"]


@pytest.mark.parametrize("recompute", (False, True))
def test_state_space_cost_by_hand(recompute):
    cost = kernel_cost_ssm.state_space_train(
        8192, 128, 64, 128, 8, 5, recompute=recompute)
    assert cost["flops"] == 8192 * 5 * 128 * 3 * 4 * 64 * 128
    # x and y of 128 x 64, B and C of 8 x 128 in bf16; two float32
    # scalars a head.
    forward = 128 * 64 * 2 + 2 * 8 * 128 * 2 + 2 * 128 * 4 + 128 * 64 * 2
    backward = (2 * (128 * 64 * 2 + 2 * 8 * 128 * 2) + 2 * 2 * 128 * 4
                + 128 * 64 * 2)
    assert (forward, backward) == (37888, 59392)
    assert cost["bytes"] == 8192 * 5 * ((2 if recompute else 1) * forward
                                        + backward)
    v5e = peaks_for("TPU v5 lite")
    seconds, bound = kernel_cost.roofline_seconds(
        cost, peak(v5e, "bf16_flops_per_s"), peak(v5e, "hbm_bytes_per_s"))
    assert bound == "memory"
    assert seconds == pytest.approx(6.76e-3 if recompute else 4.87e-3,
                                    rel=0.01)
    # Independent of any chunk length: no argument names one.
    assert "chunk" not in " ".join(
        kernel_cost_ssm.state_space_train.__code__.co_varnames)
