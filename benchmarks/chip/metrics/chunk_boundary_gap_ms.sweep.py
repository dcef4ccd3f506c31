"""Device idle time between consecutive chunk programs of one batch, in ms.

Within each ``simulate`` span of the window, the time from the end of one
chunk program's execution on chip 0 to the start of the next: the host
sync on the stop times and the next dispatch.  Sum over sum: all the gaps
over their number.
"""


def read(run):
    lo, hi = run.trace.window
    execs = sorted((e for e in run.trace.modules.get(0, ())
                    if run.info["chunk_program"] in e.name), key=lambda e: e.start)
    total, count = 0, 0
    for span in run.trace.spans_named("simulate"):
        if not lo <= span.start < hi:
            continue
        inside = [e for e in execs if span.start <= e.start < span.end]
        for a, b in zip(inside, inside[1:]):
            total += max(b.start - a.end, 0)
            count += 1
    return total / 1e6 / count if count else None
