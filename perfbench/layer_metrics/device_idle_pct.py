"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    r = ctx["reduced"]
    if not r.get("window_s"):
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
