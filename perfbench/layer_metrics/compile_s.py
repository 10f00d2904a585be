"""Seconds of ``lowered.compile()``: the compiler cold, a read of the
persistent cache warm."""


def read(ctx):
    return ctx["timings"].get("compile_s")
