"""Milliseconds per step under ``mlp/moe_router``, ``mlp/moe_dispatch``
and ``mlp/moe_combine``: router matmul, softmax and top-k, the sort, the
counts and both row gathers with their backward passes — what a dense MLP
does not pay, and what a later ``perf_opt`` shrinks."""

from perfbench import moe_reduce


def read(ctx):
    return moe_reduce.part_ms(
        ctx, ("moe_router", "moe_dispatch", "moe_combine"))
