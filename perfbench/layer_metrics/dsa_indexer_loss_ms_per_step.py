"""Milliseconds per step in the indexer's loss (``dsa_index_loss``): the
head-mean of the attention's probabilities (the ``dsa_probs`` kernel), the
KL against the softmax of the scores over the selected keys and its
gradient with respect to the scores, every phase, on one device."""

from perfbench import dsa_reduce


def read(ctx):
    return dsa_reduce.part_ms(ctx, (dsa_reduce.INDEX_LOSS,))
