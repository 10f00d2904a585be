"""Milliseconds per step in which a collective operation ran on a device
and no other operation did: what overlapping the exchange with the
backward pass could still hide."""


def read(ctx):
    r = ctx["reduced"]
    if not r or r["devices"] < 2:
        return None
    return r["exposed_collective_s"] * 1e3 / ctx["trace_steps"]
