"""Device time of a fleet step's dispatch of its arrivals, per grid step, in us.

The chunk program's leaf ops on chip 0, inside its executions in the
window, whose HLO names the program maps to the step phase ``dispatch``
(``chunk_op_scopes``): their durations summed, over executions x steps per
chunk.  A program without the phase has no such op: nothing to read.
"""

from benchmarks.chip.program_spans import phase_device_us


def read(run):
    return phase_device_us(run, "dispatch") or None
