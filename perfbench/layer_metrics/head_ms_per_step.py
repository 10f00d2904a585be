"""Milliseconds per step under the scopes ``head`` (final norm and the
tied-head matmul) and ``loss``, every phase."""

from perfbench import scope_reduce


def read(ctx):
    return scope_reduce.scope_ms(ctx, ("head", "loss"))
