"""Host time of a fleet cell's sweep front end per batch, in ms.

The quantity ``host_prep_ms_per_batch.sweep`` reads, in the fleet cell.
"""

import os

from benchmarks.chip.run import read_metric


def read(run):
    return read_metric(os.path.dirname(os.path.dirname(__file__)), "host_prep_ms_per_batch.sweep",
                       run)
