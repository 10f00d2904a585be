"""Milliseconds per step around the flash kernels of latent attention:
everything under ``attn/qkv`` (the norm, ``mla_q``, ``mla_kv``,
``mla_rope``) and ``attn/out`` (the out projection of ``heads x head
width`` and the residual add), every phase, on one device."""

from perfbench import mla_reduce


def read(ctx):
    return mla_reduce.scope_ms(ctx, mla_reduce.PROJECTION_SCOPES)
