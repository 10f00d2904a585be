"""``hybrid_lm.train_flops`` and ``kernel_cost_gdn`` against counts made
from shapes at the cell's sizes."""

import json
import os

import jax
import pytest

from horovod_tpu.models import transformer as tfm
from perfbench import kernel_cost, kernel_cost_gdn, run
from perfbench.adapters import hybrid_lm
from perfbench.peaks import peak, peaks_for


def _config():
    with open(os.path.join(run.HERE, "configs",
                           "olmo-hybrid-7b.json")) as f:
        return json.load(f)


def test_hybrid_lm_train_flops_by_hand():
    config = _config()
    assert hybrid_lm.layer_types(config) == (
        "linear_attention",) * 3 + ("full_attention",)
    # Linear mixer: Wq, Wk 3840 x 2880; Wv, Wz, Wo 3840 x 5760; Wa, Wb
    # 3840 x 30.  Full mixer: four 3840 x 3840.  MLP: three 3840 x 11008.
    linear = 3840 * (2 * 2880 + 3 * 5760 + 2 * 30)
    assert linear == 88_704_000
    full, mlp, head = 4 * 3840 ** 2, 3 * 3840 * 11008, 3840 * 25088
    assert (full, mlp, head) == (58_982_400, 126_812_160, 96_337_920)
    weights = 3 * (linear + mlp) + (full + mlp) + head
    tokens = 16384
    by_hand = (6 * weights * tokens + 6 * 16384 ** 2 * 3840
               + 3 * (3 * 6 * 96 * 192 * 30) * tokens)
    assert hybrid_lm.train_flops(config, 16384, 1) == by_hand
    assert 97e12 < by_hand < 99e12
    # Two sequences: everything doubles (attention is per sequence).
    assert hybrid_lm.train_flops(config, 16384, 2) == 2 * by_hand


def test_matmul_parameters_are_the_models_matrices():
    """Against the program's own parameter tree at the rehearsal size:
    every leaf with two axes but the embedding and the convolution."""
    config = run._load(os.path.join(run.HERE, "configs",
                                    "olmo-hybrid-7b.json"), rehearse=True)
    params = tfm.init_abstract(hybrid_lm.model_config(config, 256))
    counted = hybrid_lm.matmul_parameters(config)

    def matrices(tree, skip=()):
        return sum(leaf.size for name, leaf in tree.items()
                   if leaf.ndim == 2 and name not in skip)

    mixer = lambda layer: matrices(
        {k: v for k, v in layer.items()
         if k not in ("w_gate", "w_up", "w_down")}, skip=("lin_conv",))
    assert mixer(params["layers"][0]) == counted["linear_attention"]
    assert mixer(params["layers"][3]) == counted["full_attention"]
    assert matrices({k: params["layers"][0][k]
                     for k in ("w_gate", "w_up", "w_down")}) == counted["mlp"]
    assert params["head"].size == counted["head"]


@pytest.mark.parametrize("recompute", (False, True))
def test_gated_delta_rule_cost_by_hand(recompute):
    cost = kernel_cost_gdn.gated_delta_rule_train(
        16384, 30, 96, 192, 3, recompute=recompute)
    rows = 16384 * 30 * 3
    assert cost["flops"] == rows * 3 * 6 * 96 * 192
    # q, k of 96 and v of 192 in bf16; two float32 gates; o of 192.
    forward = (96 + 96 + 192) * 2 + 8 + 192 * 2
    backward = 2 * (96 + 96 + 192) * 2 + 2 * 8 + 192 * 2
    assert (forward, backward) == (1160, 1936)
    assert cost["bytes"] == rows * ((2 if recompute else 1) * forward
                                    + backward)
    v5e = peaks_for("TPU v5 lite")
    seconds, bound = kernel_cost.roofline_seconds(
        cost, peak(v5e, "bf16_flops_per_s"), peak(v5e, "hbm_bytes_per_s"))
    assert bound == "memory"
    assert seconds == pytest.approx(7.66e-3 if recompute else 5.57e-3,
                                    rel=0.01)
    # Independent of any block length: no argument names one.
    assert "block" not in kernel_cost_gdn.gated_delta_rule_train.__code__.\
        co_varnames
