"""Device idle time between consecutive chunk programs of one fleet batch, in ms.

The quantity ``chunk_boundary_gap_ms.sweep`` reads, in the fleet cell.
"""

import os

from benchmarks.chip.run import read_metric


def read(run):
    return read_metric(os.path.dirname(os.path.dirname(__file__)), "chunk_boundary_gap_ms.sweep",
                       run)
