"""Device time of the step's merged write-back scatters, per grid step, in us.

As ``edf_rank_device_us.sweep``, for the ops of step phase ``writeback``.
"""

from benchmarks.chip.program_spans import phase_device_us


def read(run):
    return phase_device_us(run, "writeback")
