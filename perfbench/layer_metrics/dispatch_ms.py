"""Median milliseconds from the call of one ``step()`` to its return to
Python, over the untraced chunk that precedes the traced window."""

import statistics


def read(ctx):
    calls = ctx["dispatch_s"]
    return statistics.median(calls) * 1e3 if calls else None
