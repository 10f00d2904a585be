"""Milliseconds per step in the indexer: its three projections and
rotation (``dsa_index_proj``) and its scores over every causal pair with
their gradient (``dsa_index_scores``), every phase, on one device."""

from perfbench import dsa_reduce


def read(ctx):
    return dsa_reduce.part_ms(ctx, (dsa_reduce.INDEX_PROJ,
                                    dsa_reduce.INDEX_SCORES))
