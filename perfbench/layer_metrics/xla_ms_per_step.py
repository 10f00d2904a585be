"""Milliseconds per step of device time that is neither a named kernel of
the cell nor a collective: what XLA's own fusions, copies and loops take."""


def read(ctx):
    r = ctx["reduced"]
    if not r:
        return None
    rest = (sum(r["op_s"].values()) - sum(r["kernel_s"].values())
            - r["collective_s"])
    return rest * 1e3 / ctx["trace_steps"]
