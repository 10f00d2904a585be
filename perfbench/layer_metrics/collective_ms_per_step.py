"""Milliseconds per step in collective operations on one device."""


def read(ctx):
    r = ctx["reduced"]
    if not r or r["devices"] < 2:
        return None
    return r["collective_s"] * 1e3 / ctx["trace_steps"]
