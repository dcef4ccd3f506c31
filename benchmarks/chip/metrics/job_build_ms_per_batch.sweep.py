"""Host time of building the jobs of a batch's days from their arrivals, in ms.

The program's ``scenario.jobs`` spans (``jobs_from_arrivals``, the per-job
attribute draws), summed, over the number of ``batched.simulate`` spans.
"""

from benchmarks.chip.program_spans import mean_ms


def read(run):
    return mean_ms(run, "scenario.jobs", per="batched.simulate")
