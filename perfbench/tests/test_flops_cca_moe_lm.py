"""``cca_moe_lm.train_flops`` and ``kernel_cost_cca`` against hand counts
at the cell's sizes."""

import json
import os

import pytest

from perfbench import kernel_cost, kernel_cost_cca, kernel_cost_moe, run
from perfbench.adapters import cca_moe_lm

CONFIG = os.path.join(run.HERE, "configs", "zaya1-8b.json")


@pytest.fixture(scope="module")
def config():
    return run._load(CONFIG, rehearse=False)


def test_matmul_parameters_by_hand(config):
    n = cca_moe_lm.matmul_parameters(config)
    # W_q 2048 x 1024, W_k 2048 x 256, two value projections 2048 x 128,
    # W_o 1024 x 2048.
    assert n["attention"] == (2048 * 1024 + 2048 * 256 + 2 * 2048 * 128
                              + 1024 * 2048) == 5_242_880
    # The grouped convolution is a matmul: 2 taps x 10 heads x 128 x 128.
    # The depthwise one (2 x 1280 numbers) is not, and is not counted.
    assert n["grouped_conv"] == 2 * 10 * 128 * 128 == 327_680
    assert n["router"] == (2048 * 256 + 2 * 256 * 256 + 256 * 17
                           ) == 659_712
    # 8 / 17 of one expert a token; the skip multiplies nothing.
    assert n["experts"] == pytest.approx(8 / 17 * 3 * 2048 * 2048)
    assert n["head"] == 2048 * 32_784


def test_train_flops_by_hand(config):
    layers, t = config["num_hidden_layers"], 16_384
    a_layer = 5_242_880 + 327_680 + 659_712 + 8 / 17 * 12_582_912
    want = (6 * t * (layers * a_layer + 2048 * 32_784)
            + 12 * (t * (t + 1) // 2) * 8 * 128 * layers)
    assert cca_moe_lm.train_flops(config, t, 1) == pytest.approx(want)
    # Two sequences: twice.
    assert cca_moe_lm.train_flops(config, t, 2) == pytest.approx(2 * want)
    # At eleven layers: 37.9 TFLOP, 48% of it attention's pairs.
    eleven = dict(config, num_hidden_layers=11)
    total = cca_moe_lm.train_flops(eleven, t, 1)
    assert total == pytest.approx(37.88e12, rel=1e-3)
    pairs = 12 * (t * (t + 1) // 2) * 8 * 128 * 11
    assert pairs / total == pytest.approx(0.479, abs=2e-3)


def test_the_depth_and_the_vocabulary_scale_their_terms_alone(config):
    t = 16_384
    base = cca_moe_lm.train_flops(config, t, 1)
    deeper = cca_moe_lm.train_flops(
        dict(config, num_hidden_layers=config["num_hidden_layers"] + 1), t,
        1)
    a_layer = 5_242_880 + 327_680 + 659_712 + 8 / 17 * 12_582_912
    assert deeper - base == pytest.approx(
        6 * t * a_layer + 12 * (t * (t + 1) // 2) * 1024)
    wider = cca_moe_lm.train_flops(dict(config, vocab_size=65_568), t, 1)
    assert wider - base == pytest.approx(6 * t * 2048 * 32_784)


def test_flash_cost_counts_key_value_heads_once():
    cost = kernel_cost_cca.grouped_causal_attention_train(1, 8, 2, 16_384,
                                                          128, 11)
    plain = kernel_cost.causal_attention_train(1, 8, 16_384, 128)
    assert cost["flops"] == 11 * plain["flops"]
    q = 8 * 16_384 * 128 * 2
    kv = 2 * 16_384 * 128 * 2
    stats = 2 * 8 * 16_384 * 4
    assert cost["bytes"] == 11 * (6 * q + 6 * kv + 2 * stats)
    assert cost["bytes"] < 11 * plain["bytes"]


def test_expert_cost_is_the_uniform_routers_rows():
    cost = kernel_cost_cca.one_of_seventeen_experts_train(16_384, 8, 17,
                                                          2048, 2048, 11)
    rows = 16_384 * 8 // 17
    assert rows == 7_710
    assert cost == kernel_cost_moe.expert_matmuls_train(rows, 2048, 2048, 8,
                                                        11)
    assert cost["flops"] == 11 * 9 * 2 * rows * 2048 * 2048


def test_benchmark_entries_are_within_the_contracts_limits():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"]
                if w["name"] == "zaya1_8b_t16k")
    entry = next(c for c in bench["configs"] if c["name"] == "zaya1-8b")
    assert cell == dict(cell, config="zaya1-8b", traffic="t16384_b1_zipf",
                        chips=1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == ["zaya1_8b_t16k"]]
    assert len(mine) == 10 and {m["moves"] for m in mine} == {"mfu_pct"}
    for m in mine:
        assert os.path.exists(os.path.join(
            run.HERE, "layer_metrics", m["name"] + ".py")), m["name"]
