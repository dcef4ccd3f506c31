"""Bytes of host arrays handed to the device per chunk, in MB.

The ``bytes`` counted on the program's ``chunk.upload`` spans, summed,
over their number, / 1e6.
"""

from benchmarks.chip.program_spans import spans_named


def read(run):
    spans = spans_named(run, "chunk.upload")
    if not spans:
        return None
    return sum(s.counts["bytes"] for s in spans) / len(spans) / 1e6
