"""The indexer's score kernels' share of their roofline: the least time the
chip could take for the scores of every causal pair forward and of the
selected pairs backward (``perfbench.kernel_cost_dsa.indexer_scores_train``)
over the time ``dsa_index_fwd`` and ``dsa_index_bwd`` took, the recomputed
forward included in the time and not in the need."""

from perfbench import dsa_reduce


def read(ctx):
    return dsa_reduce.kernel_roofline(ctx, "dsa_index",
                                      "dsa_indexer_roofline")
