"""Device time of a fleet step's per-GPU EDF rank search and reassignment,
per grid step, in us.

As ``dispatch_device_us.fleet``, for the ops of step phase ``edf_rank``.
"""

from benchmarks.chip.program_spans import phase_device_us


def read(run):
    return phase_device_us(run, "edf_rank")
