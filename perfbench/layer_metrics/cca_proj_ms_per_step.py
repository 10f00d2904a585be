"""Milliseconds per step in the five projections of compressed
convolutional attention (``W_q``, ``W_k``, the two value projections,
``W_o``) and its first norm, every phase, on one device."""

from perfbench import cca_reduce


def read(ctx):
    return cca_reduce.part_ms(ctx, ("proj",))
