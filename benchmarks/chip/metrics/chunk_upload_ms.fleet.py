"""Host time of one fleet chunk's upload of the job and policy arrays, in ms.

The quantity ``chunk_upload_ms.sweep`` reads, in the fleet cell.
"""

import os

from benchmarks.chip.run import read_metric


def read(run):
    return read_metric(os.path.dirname(os.path.dirname(__file__)), "chunk_upload_ms.sweep", run)
