"""Host time of one chunk's upload of the job and policy arrays, in ms.

The program's ``chunk.upload`` spans (around the ``jnp.asarray`` calls of
``run_steps``), summed and divided by their number.  A host number: where
the copy is asynchronous it times the enqueue.
"""

from benchmarks.chip.program_spans import mean_ms


def read(run):
    return mean_ms(run, "chunk.upload")
