"""``dsa_moe_lm.train_flops`` against a count made from shapes at the
cell's sizes, term by term, and the sparse-attention kernels' costs."""

import json
import os

from horovod_tpu.models import transformer as tfm
from perfbench import kernel_cost, kernel_cost_dsa, run
from perfbench.adapters import dsa_moe_lm
from perfbench.peaks import peak, peaks_for

CONFIG = os.path.join(run.HERE, "configs", "keye-vl-2.0-30b-a3b.json")
T = 16384
# Pairs the queries of one 16384-token sequence select, 2048 at most each,
# and the pairs under the causal diagonal.
PAIRS = 2048 * 2049 // 2 + (T - 2048) * 2048
CAUSAL = T * (T + 1) // 2


def _config():
    with open(CONFIG) as f:
        return json.load(f)


def test_selected_pairs_by_hand():
    assert PAIRS == 31_458_304
    assert kernel_cost_dsa.selected_pairs(T, 2048) == PAIRS
    # Under topk every earlier key; the mean query of the cell keeps 1920.
    assert kernel_cost_dsa.selected_pairs(100, 2048) == 100 * 101 // 2
    assert kernel_cost_dsa.selected_pairs(2048, 2048) == 2048 * 2049 // 2
    assert round(PAIRS / T) == 1920
    assert abs(PAIRS / CAUSAL - 0.2344) < 1e-3


def test_dsa_moe_lm_train_flops_by_hand():
    config = _config()
    layers = config["num_hidden_layers"]
    # W_q 2048 x (32 x 128), W_k and W_v 2048 x (4 x 128), W_o 4096 x 2048.
    attention = 2 * 2048 * 4096 + 2 * 2048 * 512
    assert attention == 18_874_368
    # W_qI 2048 x (16 x 64), W_kI 2048 x 64, W_w 2048 x 16.
    indexer = 2048 * (1024 + 64 + 16)
    assert indexer == 2_260_992
    # Router 2048 x 128 and 8 x 16 / 128 = 1 expert of 3 x 2048 x 768 a
    # token on this chip.
    here = 8 * 16 / 128
    assert here == 1.0
    experts = 2048 * 128 + here * 3 * 2048 * 768
    assert experts == 4_980_736
    head = 2048 * 18992
    weights = (6 * T * (layers * (attention + experts) + head)
               + 4 * T * layers * indexer)
    selected = 12 * PAIRS * 32 * 128 * layers
    scores = (2 * 1024 * CAUSAL + 4 * 1024 * PAIRS) * layers
    by_hand = weights + selected + scores
    assert dsa_moe_lm.train_flops(config, T, 1) == by_hand
    assert layers == 7 and 34.9e12 < by_hand < 35.0e12
    # Attention over the selected keys is 31% of it; over every earlier
    # key it would be 4.3 times that.
    assert 0.30 < selected / by_hand < 0.32
    assert 4.2 < CAUSAL / PAIRS < 4.3
    # The indexer, projections and scores: 11%.
    assert 0.11 < (4 * T * layers * indexer + scores) / by_hand < 0.12
    # Two sequences: everything doubles (attention is per sequence).
    assert dsa_moe_lm.train_flops(config, T, 2) == 2 * by_hand


def test_matmul_parameters_are_the_models_matrices():
    """Against the program's own parameter tree at the rehearsal size:
    every leaf with two axes but the embedding, an expert at the share of
    it a token uses here."""
    config = run._load(CONFIG, rehearse=True)
    params = tfm.init_abstract(dsa_moe_lm.model_config(config, 256))
    counted = dsa_moe_lm.matmul_parameters(config)
    attention = ("wq", "wk", "wv", "wo")
    indexer = ("index_wq", "index_wk", "index_ww")
    experts = ("w_gate", "w_up", "w_down")

    def matrices(layer, names):
        return sum(layer[name].size for name in names)

    for layer in params["layers"]:
        assert matrices(layer, attention) == counted["attention"]
        assert matrices(layer, indexer) == counted["indexer"]
        held = layer["w_up"].shape[0]
        a_token = (config["num_experts_per_tok"] * held
                   / config["num_local_experts"])
        assert (layer["router"].size
                + a_token * matrices(layer, experts) / held
                == counted["experts"])
        others = [name for name, leaf in layer.items() if leaf.ndim >= 2
                  and name not in attention + indexer + experts + (
                      "router",)]
        assert not others, others
    assert params["head"].size == counted["head"]


def test_kernel_costs_by_hand():
    """The masked attention kernels need what attention over the selected
    pairs needs, the indexer's what its scores do; both are bound by the
    MXU on a v5e."""
    v5e = peaks_for("TPU v5 lite")
    flash = kernel_cost_dsa.sparse_attention_train(1, 32, 4, T, 128, 2048)
    assert flash["flops"] == 32 * PAIRS * 7 * 2 * 128
    q_like, kv_like = 32 * T * 128 * 2, 4 * T * 128 * 2
    assert flash["bytes"] == (6 * q_like + 6 * kv_like + 2 * 32 * T * 4
                              + 8 * PAIRS)
    seconds, bound = kernel_cost.roofline_seconds(
        flash, peak(v5e, "bf16_flops_per_s"), peak(v5e, "hbm_bytes_per_s"))
    assert bound == "compute"
    # 9.2 ms a layer against the dense causal kernels' 39 ms of need.
    assert 9.1e-3 < seconds < 9.2e-3
    dense = kernel_cost.causal_attention_train(1, 32, T, 128)
    assert 4.2 < dense["flops"] / flash["flops"] < 4.3
    index = kernel_cost_dsa.indexer_scores_train(1, 16, 64, T, 2048)
    assert index["flops"] == 2 * 1024 * CAUSAL + 6 * 1024 * PAIRS
    seconds, bound = kernel_cost.roofline_seconds(
        index, peak(v5e, "bf16_flops_per_s"), peak(v5e, "hbm_bytes_per_s"))
    assert bound == "compute" and 2.3e-3 < seconds < 2.4e-3
