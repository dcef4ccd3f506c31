"""Host time of the sweep front end per batch, in ms.

The benchmark's spans around the program's scenario generation, padding
and policy compilation, summed over the batches of the window and divided
by their number.
"""


def read(run):
    lo, hi = run.trace.window
    batches = [s for s in run.trace.spans_named("batch") if lo <= s.start < hi]
    if not batches:
        return None
    ns = sum(s.end - s.start for name in run.info["host_prep_spans"]
             for s in run.trace.spans_named(name) if lo <= s.start < hi)
    return ns / 1e6 / len(batches)
