"""Share of a fleet cell's window in which no operation ran on chip 0, in %.

The quantity ``device_idle_share.sweep`` reads, in the fleet cell: one
minus the union of the chip's op intervals over the traced window.
"""

import os

from benchmarks.chip.run import read_metric


def read(run):
    return read_metric(os.path.dirname(os.path.dirname(__file__)), "device_idle_share.sweep", run)
