"""Milliseconds per step in the shared SwiGLU expert of the
sigmoid-routed expert layers, which every token passes through, every
phase, on one device."""

from perfbench import mla_reduce


def read(ctx):
    return mla_reduce.part_ms(ctx, mla_reduce.SHARED_PARTS)
