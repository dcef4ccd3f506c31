"""Device time of the scan's chunk program per grid step it advanced, in us.

Sum of the durations of the chunk program's executions on chip 0 inside the
window, over the grid steps they advanced (executions x steps per chunk).
"""


def read(run):
    lo, hi = run.trace.window
    execs = [e for e in run.trace.modules.get(0, ())
             if run.info["chunk_program"] in e.name and lo <= e.start < hi]
    if not execs:
        return None
    return sum(e.end - e.start for e in execs) / 1e3 / (len(execs) * run.info["chunk_steps"])
