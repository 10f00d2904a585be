"""``looped_lm.train_flops`` and ``kernel_cost_loop`` against counts made
from shapes at the cell's sizes: a weight counted once a use (four times),
four readouts, and the needed causal pairs times four."""

import json
import os

from horovod_tpu.models import transformer as tfm
from perfbench import kernel_cost, kernel_cost_loop, run
from perfbench.adapters import looped_lm
from perfbench.peaks import peak, peaks_for

CONFIG = os.path.join(run.HERE, "configs", "ouro-2.6b.json")


def _config():
    with open(CONFIG) as f:
        return json.load(f)


def test_looped_lm_train_flops_by_hand():
    config = _config()
    layers = config["num_hidden_layers"]
    # Wq, Wk, Wv, Wo 2048 x 2048; W_gate, W_up, W_down 2048 x 5632.
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert layer == 51_380_224
    head, gate, tokens = 2048 * 49152, 2048, 4096
    # Four uses of every layer's weights, four readouts, four gates.
    weights = 4 * (layers * layer + head + gate)
    attention = 4 * layers * 6 * 4096 ** 2 * 2048
    by_hand = 6 * weights * tokens + attention
    assert looped_lm.train_flops(config, 4096, 1) == by_hand
    # One pass is a quarter, to the FLOP: nothing is counted once a step.
    once = dict(config, total_ut_steps=1)
    assert 4 * looped_lm.train_flops(once, 4096, 1) == by_hand
    # The readouts' share of the model's work, here and in the whole model.
    share = lambda n: (head + gate) / (n * (layer + 4096 * 2048) + head
                                       + gate)
    assert 0.09 < share(layers) < 0.13 and 0.03 < share(48) < 0.04
    # Two sequences: everything doubles (attention is per sequence).
    assert looped_lm.train_flops(config, 4096, 2) == 2 * by_hand


def test_matmul_parameters_are_the_models_matrices():
    """Against the program's own parameter tree at the rehearsal size:
    every leaf with two axes but the embedding."""
    config = run._load(CONFIG, rehearse=True)
    params = tfm.init_abstract(looped_lm.model_config(config, 256))
    counted = looped_lm.matmul_parameters(config)
    assert sum(leaf.size for leaf in params["layers"][0].values()
               if leaf.ndim == 2) == counted["layer"]
    assert params["head"].size == counted["head"]
    assert params["exit_gate_w"].size == counted["gate"]
    assert set(params) == {"embed", "head", "ln_f_scale", "layers",
                           "exit_gate_w", "exit_gate_b"}


def test_flash_cost_counts_every_pass_and_no_recomputation():
    config = _config()
    layers = config["num_hidden_layers"]
    cost = kernel_cost_loop.looped_causal_attention_train(
        1, 16, 4096, 128, layers, 4)
    pairs = 4096 * 4097 // 2
    assert kernel_cost_loop.needed_pairs(4096, layers, 4) == (
        pairs * layers * 4)
    # 2 matmul terms forward and 5 backward of 2 x 128 FLOPs a pair.
    assert cost["flops"] == 16 * pairs * layers * 4 * 7 * 2 * 128
    once = kernel_cost.causal_attention_train(1, 16, 4096, 128)
    assert cost["bytes"] == once["bytes"] * layers * 4
    peaks = peaks_for("TPU v5 lite")
    ideal, bound = kernel_cost.roofline_seconds(
        cost, peak(peaks, "bf16_flops_per_s"), peak(peaks, "hbm_bytes_per_s"))
    assert bound == "compute"
