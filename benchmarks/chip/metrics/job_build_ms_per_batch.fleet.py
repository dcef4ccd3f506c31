"""Host time of building the jobs of a fleet batch's server-days, in ms.

The quantity ``job_build_ms_per_batch.sweep`` reads, in the fleet cell.
"""

import os

from benchmarks.chip.run import read_metric


def read(run):
    return read_metric(os.path.dirname(os.path.dirname(__file__)), "job_build_ms_per_batch.sweep",
                       run)
