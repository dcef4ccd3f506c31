"""The fleet-sweep runner: one unit of work is one batch of fleet rollouts.

A fleet is the configuration's ``devices`` identical GPUs behind one job
stream and its ``dispatcher``.  A batch is what a sweep user asks the
program for: ``batch`` server-days of the mix, one per seed, simulated to
their stop times.  Its calls into the program are those of the seed-sweep
runner (``sweep.py``: generate, pad, compile_policy, simulate, result), with
``simulate_batch`` run on the device axis (``devices``, ``dispatcher``), as
``repro.sweep.batched.run_batched_cells`` runs a fleet cell.

The check compares each sampled row with the fleet reference
(``reference_fleet.py``) on the benchmark's own day of the same seed: the
seed-sweep runner's numbers, summed over the fleet, and ``dispatch_differ``,
the share of the row's jobs routed to another GPU than the reference routed
them.  The reference routes a near-tie of backlogs, two that differ by no
more than their float32 rounding, the way the compared answer did, so that
a rounding does not route the rest of the day apart; equal backlogs it
gives to the lowest index (``reference_fleet.py``).  Its control is the
reference with round-robin in place of the configuration's dispatcher.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np

from benchmarks.chip import reference, reference_fleet, sweep, traffic

SPANS = sweep.SPANS

#: the compared numbers that count rows; every other is the widest over rows
SUMMED = ("jobs_differ", "counts_differ")

#: the reference's dispatcher that breaks the configuration's dispatch rule
CONTROL = "round-robin"


@dataclass
class FleetSample(sweep.Sample):
    device: np.ndarray  # the program's GPU of each job of the row


class Runner(sweep.Runner):
    """Set-up, units and check of a fleet cell."""

    def __init__(self, config: dict, mix: dict, seed: int, annotate) -> None:
        super().__init__(config, mix, seed, annotate)
        self.devices = int(config["devices"])
        self.dispatcher = config["dispatcher"]

    def _simulate(self, days):
        from repro.core.batched import BatchedJobs, compile_policy, simulate_batch
        from repro.sweep.cells import make_policy

        pol = self.config["policy"]
        longest = max(len(d) for d in days)
        if longest > self.padded:
            raise ValueError(
                f"a day of {longest} jobs is over the mix's fixed padded_jobs {self.padded}"
            )
        with self.annotate("pad"):
            jobs = BatchedJobs.from_job_lists(
                days, max_slots=self.tables.max_slots,
                mig_enabled=self.config["device"]["mig_enabled"], min_jobs=self.padded,
            )
        with self.annotate("compile_policy"):
            policy = compile_policy(
                make_policy(pol["name"], {"day_config": pol["day_config"],
                                          "night_config": pol["night_config"]}),
                self.tables, batch=self.batch,
            )
        with self.annotate("simulate"):
            res = simulate_batch(
                jobs, policy, tables=self.tables,
                repartition_mode=self.config["repartition_mode"],
                dt_min=self.config["grid"]["dt_min"],
                devices=self.devices, dispatcher=self.dispatcher,
            )
        with self.annotate("result"):
            out = res.to_result_dicts()
        return jobs, res, out

    def unit(self, k: int) -> int:
        """Run batch ``k``; keeps its sampled rows; returns the days done."""
        with self.annotate("batch"):
            with self.annotate("generate"):
                seeds, days = self._days(k)
            jobs, res, out = self._simulate(days)
        for b in self.sample_rows(k, [len(d) for d in days]):
            self.samples.append(FleetSample(
                seed=seeds[b],
                jobs={f: getattr(jobs, f)[b].copy()
                      for f in (*sweep._DAY_FIELDS, "rate_by_slots", "valid")},
                completion=res.completion[b].copy(),
                result=out[b],
                device=np.asarray(res.device[b]).copy(),
            ))
        return self.batch

    # -- the check -------------------------------------------------------
    def reference_rows(self, dispatcher: str = "", follow=None, precision=None) -> List[dict]:
        """The fleet reference's answer for every sampled row, from its own
        day; ``dispatcher`` in place of the configuration's (the control);
        near-ties resolved as the rows ``follow`` routed them; computed at
        ``precision`` in place of float64."""
        device = reference.build_device(self.config)
        rows = []
        for i, s in enumerate(self.samples):
            day = traffic.generate_day(self.mix, s.seed, device.max_slots,
                                       self.config["device"]["mig_enabled"])
            out = reference_fleet.simulate(day, self.config, device, dispatcher,
                                           None if follow is None else follow[i]["device"],
                                           precision)
            out["day"] = day
            rows.append(out)
        return rows

    def control_rows(self) -> List[dict]:
        """The control's rows, in the program's place."""
        rows = self.reference_rows(CONTROL)
        for row in rows:
            row["inputs"] = sweep.inputs_of(row["day"])
        return rows

    def control_check(self, limits: Dict[str, float]):
        """The check's numbers with the control in the program's place."""
        rows = self.control_rows()
        return gaps(rows, self.reference_rows(follow=rows), limits)

    def low_precision_check(self, limits: Dict[str, float], precision):
        """The check's numbers with the reference computed at ``precision``
        (below the program's float32) in the program's place."""
        rows = self.reference_rows(precision=precision)
        for row in rows:
            row["inputs"] = {f: a.astype(precision).astype(np.float32)
                             for f, a in sweep.inputs_of(row["day"]).items()}
        return gaps(rows, self.reference_rows(follow=rows), limits)

    def program_rows(self) -> List[dict]:
        rows = super().program_rows()
        for row, s in zip(rows, self.samples, strict=True):
            row["device"] = s.device[: int(s.jobs["valid"].sum())]
        return rows

    def check(self, limits: Dict[str, float]):
        """The widest gap of each compared number, and the rows over a limit."""
        rows = self.program_rows()
        return gaps(rows, self.reference_rows(follow=rows), limits)


def row_gaps(got: dict, ref: dict) -> Dict[str, float]:
    """The seed-sweep runner's numbers for one row, and ``dispatch_differ``."""
    out = sweep.row_gaps(got, ref)
    m = min(len(got["device"]), len(ref["device"]))
    out["dispatch_differ"] = (float(np.mean(got["device"][:m] != ref["device"][:m]))
                              if m else 0.0)
    return out


def gaps(got_rows: List[dict], ref_rows: List[dict], limits: Dict[str, float]):
    """Widest gap of each number over the rows (the counts of ``SUMMED`` are
    summed), and how many rows miss a limit."""
    worst: Dict[str, float] = {k: 0 for k in limits}
    failed = 0
    for got, ref in zip(got_rows, ref_rows, strict=True):
        g = row_gaps(got, ref)
        failed += int(any(g[k] > limits[k] for k in limits))
        for k in limits:
            worst[k] = worst[k] + g[k] if k in SUMMED else max(worst[k], g[k])
    return worst, failed
