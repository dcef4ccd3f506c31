"""Bytes of host arrays handed to the device per fleet chunk, in MB.

The quantity ``h2d_mb_per_chunk.sweep`` reads, in the fleet cell.
"""

import os

from benchmarks.chip.run import read_metric


def read(run):
    return read_metric(os.path.dirname(os.path.dirname(__file__)), "h2d_mb_per_chunk.sweep", run)
