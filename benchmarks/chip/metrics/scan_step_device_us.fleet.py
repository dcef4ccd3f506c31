"""Device time of a fleet cell's chunk program per grid step it advanced, in us.

The quantity ``scan_step_device_us.sweep`` reads, in the fleet cell: the
chunk program's executions on chip 0 inside the window, over the grid
steps they advanced.
"""

import os

from benchmarks.chip.run import read_metric


def read(run):
    return read_metric(os.path.dirname(os.path.dirname(__file__)), "scan_step_device_us.sweep",
                       run)
