"""The seed-sweep runner: one unit of work is one batch of rollouts.

A batch is what a sweep user asks the program for: ``batch`` days of the
mix, one per seed, simulated to their stop times.  Its calls into the
program are the ones ``repro.sweep.batched.run_batched_cells`` makes,
composed here so that each sits in a span of its own and the job axis is
padded to the mix's fixed ``padded_jobs`` (one compiled program for the
whole run): generate (``cell_jobs``), pad (``BatchedJobs.from_job_lists``),
compile_policy, simulate (``simulate_batch``, its chunk programs and the
host sync after each) and result (``to_result_dicts``).

The check runs after the window: for sampled rows of every batch (the row
with the most jobs, and one drawn from the seed in each half of the batch)
the benchmark's own generator makes the same day, the plain reference
simulates it, and each number in ``cells/<workload>.json`` is compared
with its limit.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np

from benchmarks.chip import reference, traffic

SPANS = ("batch", "generate", "pad", "compile_policy", "simulate", "result")

_DAY_FIELDS = ("arrival", "deadline", "work")


def row_seed(seed: int, batch: int, row: int) -> int:
    """The scenario seed of row ``row`` of batch ``batch`` (``-1`` = warm-up)."""
    words = np.random.SeedSequence([seed % 2**64, batch + 1, row]).generate_state(2)
    return int(words[0]) << 32 | int(words[1])


@dataclass
class Sample:
    seed: int
    jobs: Dict[str, np.ndarray]  # the program's padded row: its inputs as it ran them
    completion: np.ndarray  # the program's completion time of each job of the row
    result: dict  # the program's result dict for the row


class Runner:
    """Set-up, units and check of a sweep cell."""

    def __init__(self, config: dict, mix: dict, seed: int, annotate) -> None:
        self.config, self.mix, self.seed = config, mix, seed
        self.annotate = annotate
        self.batch = int(config["batch"])
        self.padded = int(mix["padded_jobs"])
        self.samples: List[Sample] = []

    # -- the program's calls ---------------------------------------------
    def setup(self) -> None:
        from repro.core.batched import build_tables
        from repro.sweep.cells import cell_jobs

        self.tables = build_tables()
        if self.tables.max_slots != self.config["device"]["total_slots"]:
            raise ValueError("the program's device table is not the configuration's")
        # warm-up: a batch of the window's shapes, every row the first hour of
        # one day, so that it ends at the first boundary after two chunks
        scen = self.mix["program_scenario"]
        day = cell_jobs({"scenario": {"name": scen["name"], "kwargs": scen["kwargs"]},
                         "seed": row_seed(self.seed, -1, 0)})
        first = [j for j in day if j.arrival < 60.0] or day[:1]
        self._simulate([first] * self.batch)

    def _days(self, k: int):
        from repro.sweep.cells import cell_jobs

        scen = self.mix["program_scenario"]
        seeds = [row_seed(self.seed, k, b) for b in range(self.batch)]
        cells = [{"scenario": {"name": scen["name"], "kwargs": scen["kwargs"]}, "seed": s}
                 for s in seeds]
        return seeds, [cell_jobs(c) for c in cells]

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
            self.samples.append(Sample(
                seed=seeds[b],
                jobs={f: getattr(jobs, f)[b].copy()
                      for f in (*_DAY_FIELDS, "rate_by_slots", "valid")},
                completion=res.completion[b].copy(),
                result=out[b],
            ))
        return self.batch

    def sample_rows(self, k: int, lengths: List[int]) -> List[int]:
        """The longest row and one row drawn from the seed in each half."""
        rng = np.random.default_rng([self.seed % 2**64, k, 7])
        half = self.batch // 2
        rows = [int(np.argmax(lengths)), int(rng.integers(0, half)),
                int(rng.integers(half, self.batch))]
        return sorted(set(rows))

    # -- what the harness reads -----------------------------------------
    def info(self) -> dict:
        from repro.core.batched import DEFAULT_CHUNK_STEPS

        return {"chunk_steps": DEFAULT_CHUNK_STEPS, "chunk_program": "run_chunk",
                "host_prep_spans": ("generate", "pad", "compile_policy")}

    @staticmethod
    def end_to_end(work: float, window_s: float) -> Dict[str, float]:
        return {"rollout_days_per_s": work / window_s}

    # -- the check -------------------------------------------------------
    def reference_rows(self, order: str = "edf") -> List[dict]:
        """The reference's answer for every sampled row, from its own day."""
        device = reference.build_device(self.config)
        rows = []
        for s in self.samples:
            day = traffic.generate_day(self.mix, s.seed, device.max_slots,
                                       self.config["device"]["mig_enabled"])
            out = reference.simulate(day, self.config, device, order=order)
            out["day"] = day
            rows.append(out)
        return rows

    def program_rows(self) -> List[dict]:
        """The program's answer for every sampled row, in the reference's terms."""
        rows = []
        for s in self.samples:
            n = int(s.jobs["valid"].sum())
            r = s.result
            rows.append({
                "num_jobs": r["num_jobs"], "energy_wh": r["energy_wh"],
                "tardiness_integral": r["extra"]["tardiness_integral"],
                "busy_slot_minutes": r["busy_slot_minutes"],
                "preemptions": r["preemptions"], "repartitions": r["repartitions"],
                "makespan_min": r["extra"]["makespan_min"],
                "completion": np.asarray(s.completion[:n], dtype=float),
                "inputs": {f: s.jobs[f][:n] for f in (*_DAY_FIELDS, "rate_by_slots")},
            })
        return rows

    def check(self, limits: Dict[str, float]):
        """The widest gap of each compared number, and the rows over a limit."""
        return gaps(self.program_rows(), self.reference_rows("edf"), limits)


def inputs_of(day) -> Dict[str, np.ndarray]:
    """A reference day as the float32 arrays the timed path takes."""
    f32 = np.float32
    return {"arrival": day.arrival.astype(f32), "deadline": day.deadline.astype(f32),
            "work": day.work.astype(f32), "rate_by_slots": day.rates.astype(f32)}


def row_gaps(got: dict, ref: dict) -> Dict[str, float]:
    """Each compared number for one row: ``got`` against the reference ``ref``."""
    want = inputs_of(ref["day"])
    same = all(got["inputs"][f].shape == want[f].shape
               and np.array_equal(got["inputs"][f], want[f]) for f in want)
    out = {
        "jobs_differ": int(not same),
        "counts_differ": int(got["num_jobs"] != ref["num_jobs"]
                             or got["repartitions"] != ref["repartitions"]
                             or got["makespan_min"] != ref["makespan_min"]),
        "preemption_gap": abs(int(got["preemptions"]) - ref["preemptions"])
        / max(ref["preemptions"], 10),
    }
    for name, key in (("energy_gap", "energy_wh"), ("tardiness_gap", "tardiness_integral"),
                      ("busy_gap", "busy_slot_minutes")):
        out[name] = float(abs(got[key] - ref[key]) / max(abs(ref[key]), 1.0))
    m = min(len(got["completion"]), len(ref["completion"]))
    out["completion_gap_min"] = (
        float(np.mean(np.abs(got["completion"][:m] - ref["completion"][:m]))) if m else 0.0
    )
    return out


def gaps(got_rows: List[dict], ref_rows: List[dict], limits: Dict[str, float]):
    """Widest gap of each number over the rows (counts are summed), and how
    many rows miss a limit."""
    worst: Dict[str, float] = {k: 0 for k in limits}
    failed = 0
    for got, ref in zip(got_rows, ref_rows, strict=True):
        g = row_gaps(got, ref)
        failed += int(any(g[k] > limits[k] for k in limits))
        for k in limits:
            worst[k] = worst[k] + g[k] if k.endswith("_differ") else max(worst[k], g[k])
    return worst, failed

