"""Seconds of ``step.lower(...)``: tracing the Python step to StableHLO.
Paid on every run, cache or not."""


def read(ctx):
    return ctx["timings"].get("lower_s")
