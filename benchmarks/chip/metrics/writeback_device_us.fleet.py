"""Device time of a fleet step's merged write-back scatters, per grid step, in us.

The quantity ``writeback_device_us.sweep`` reads, in the fleet cell.
"""

import os

from benchmarks.chip.run import read_metric


def read(run):
    return read_metric(os.path.dirname(os.path.dirname(__file__)), "writeback_device_us.sweep",
                       run)
