"""Share of the window in which no operation ran on chip 0, in %.

One minus the union of the chip's op intervals over the traced window.
"""


def read(run):
    lo, hi = run.trace.window
    ops = run.trace.ops.get(0)
    if ops is None or not len(ops.start):
        return None
    return 100.0 * (1.0 - run.trace.busy_ns(0) / (hi - lo))
