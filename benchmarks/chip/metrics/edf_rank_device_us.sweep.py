"""Device time of the step's EDF rank search and reassignment, per grid step, in us.

The chunk program's leaf ops on chip 0, inside its executions in the
window, whose HLO names the program maps to the step phase ``edf_rank``
(``chunk_op_scopes``): their durations summed, over executions x steps per
chunk.
"""

from benchmarks.chip.program_spans import phase_device_us


def read(run):
    return phase_device_us(run, "edf_rank")
