"""One reader per per-layer metric: ``<metric>.py`` with ``read(ctx)``.

``ctx`` is what one traced run knows:

``reduced``      ``trace_reduce.reduce_file`` of the run's trace, with the
                 cell's kernels (``Cell.kernels``) summed by name
``trace_steps``  steps inside the traced window
``timings``      the set-up stages' seconds, by name
``dispatch_s``   seconds of each ``step()`` call of the untraced chunk
``cell``         the adapter's :class:`perfbench.cell.Cell`
``peaks``        the device kind's row of ``peaks.json``

A reader that finds nothing to read returns None, and the harness leaves
the metric out of the line.
"""


def kernel_seconds(ctx, kernel: str):
    """Self seconds of the cell's ``kernel`` over the traced window on one
    device; None where the cell has no such kernel or the trace no event
    that matches it."""
    return ctx["reduced"].get("kernel_s", {}).get(kernel)
