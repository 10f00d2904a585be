"""Milliseconds per step under ``head`` (the final norm and the tied
head's matmul over this chip's rows of the vocabulary) and ``loss``, every
phase, on one device, in a program that runs compressed convolutional
attention."""

from perfbench import cca_reduce


def read(ctx):
    return cca_reduce.part_ms(ctx, ("head",))
