"""``mla_moe_lm.train_flops`` against a count made from shapes at the
cell's sizes, and the flash kernels' cost at the cell's head width."""

import json
import os

from horovod_tpu.models import transformer as tfm
from perfbench import kernel_cost, run
from perfbench.adapters import mla_moe_lm
from perfbench.peaks import peak, peaks_for

CONFIG = os.path.join(run.HERE, "configs", "glm-4.7-flash.json")


def _config():
    with open(CONFIG) as f:
        return json.load(f)


def test_mla_moe_lm_train_flops_by_hand():
    config = _config()
    # W_qa 2048 x 768, W_qb 768 x (20 x 256), W_kva 2048 x (512 + 64),
    # W_kvb 512 x (20 x (192 + 256)), W_o (20 x 256) x 2048.
    attention = (2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960
                 + 5120 * 2048)
    assert attention == 21_757_952
    dense = 3 * 2048 * 10240
    assert dense == 62_914_560
    # Router 2048 x 64, the shared expert, and 4 x 8 / 64 of a routed
    # expert of 3 x 2048 x 1536 a token on this chip.
    here = 4 * 8 / 64
    assert here == 0.5
    expert = 3 * 2048 * 1536
    experts = 2048 * 64 + expert + here * expert
    assert experts == 14_286_848
    head, combine = 2048 * 19360, 4096 * 2048
    # 11 layers and the module's one: 12 attentions, 1 dense MLP, 11
    # expert layers, the head twice.
    weights = 12 * attention + dense + 11 * experts + 2 * head + combine
    assert weights == 568_852_480
    tokens = 8192
    by_hand = 6 * weights * tokens + 12 * 6 * 8192 ** 2 * (20 * 256)
    assert mla_moe_lm.train_flops(config, 8192, 1) == by_hand
    assert 52.6e12 < by_hand < 52.8e12
    # Attention is 47% of it, the latent projections and W_o 24%.
    assert 0.46 < 12 * 6 * 8192 ** 2 * 5120 / by_hand < 0.48
    assert 0.24 < 6 * 12 * attention * tokens / by_hand < 0.25
    # Two sequences: everything doubles (attention is per sequence).
    assert mla_moe_lm.train_flops(config, 8192, 2) == 2 * by_hand


def test_matmul_parameters_are_the_models_matrices():
    """Against the program's own parameter tree at the rehearsal size:
    every leaf with two axes but the embedding, an expert at the share of
    it a token uses here."""
    config = run._load(CONFIG, rehearse=True)
    params = tfm.init_abstract(mla_moe_lm.model_config(config, 256))
    counted = mla_moe_lm.matmul_parameters(config)
    attention = ("w_qa", "w_qb", "w_kva", "w_kvb", "wo")

    def matrices(layer, names):
        return sum(layer[name].size for name in names)

    dense, experts = params["layers"][0], params["layers"][1]
    assert matrices(dense, attention) == counted["attention"]
    assert matrices(experts, attention) == counted["attention"]
    assert matrices(dense, ("w_gate", "w_up", "w_down")) == counted["dense"]
    held = experts["w_up"].shape[0]
    routed = matrices(experts, ("w_gate", "w_up", "w_down")) / held
    a_token = config["num_experts_per_tok"] * held / config["router_width"]
    assert (matrices(experts, ("router", "w_shared_gate", "w_shared_up",
                               "w_shared_down"))
            + a_token * routed == counted["experts"])
    assert params["head"].size == counted["head"]
    assert params["mtp"]["w_eh"].size == counted["mtp_combine"]
    # Every matrix of a layer is counted: nothing with two axes is left.
    for layer in (dense, experts):
        others = [name for name, leaf in layer.items()
                  if leaf.ndim >= 2 and name not in attention + (
                      "w_gate", "w_up", "w_down", "router", "w_shared_gate",
                      "w_shared_up", "w_shared_down")]
        assert not others, others


def test_flash_cost_at_head_dim_256_by_hand():
    """``kernel_cost.causal_attention_train`` takes one width for q, k
    and v: latent attention's 192 + 64 = 256 = the values' 256."""
    cost = kernel_cost.causal_attention_train(1, 20, 8192, 256)
    causal = 8192 * 8193 // 2
    assert cost["flops"] == 20 * causal * 7 * 2 * 256
    tensor = 20 * 8192 * 256 * 2
    assert cost["bytes"] == 12 * tensor + 2 * 2 * 20 * 8192 * 4
    v5e = peaks_for("TPU v5 lite")
    seconds, bound = kernel_cost.roofline_seconds(
        cost, peak(v5e, "bf16_flops_per_s"), peak(v5e, "hbm_bytes_per_s"))
    assert bound == "compute"
    # 12.2 ms a layer; the cell's twelve: 146.5 ms a step.
    assert 12.1e-3 < seconds < 12.3e-3
