"""Milliseconds per step under ``attn/flash_attention`` in a model whose
other layers are linear attention: the full-attention layers' flash
kernels (forward, its recomputation, dQ, dK+dV) and the relayouts around
them, every phase: the quadratic yardstick beside ``gdn_scan_ms_per_step``
in the same trace."""

from perfbench import scope_reduce


def read(ctx):
    return scope_reduce.scope_ms(ctx, ("attn/flash_attention",))
