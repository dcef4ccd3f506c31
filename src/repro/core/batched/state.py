"""Padded batch containers for the batched backend (numpy, jax-free).

:class:`BatchedJobs` freezes a ragged list of per-rollout job lists into
rectangular ``(B, J)`` arrays — ``J`` is the max job count rounded up to a
padding multiple so differently-sized workloads share one compiled program.
Padding rows carry ``arrival = +inf`` and ``remaining = 0`` so they are
never eligible and never accrue anything.

Elasticity curves are pre-evaluated into ``rate_by_slots[b, j, k]`` (the
work-deplete rate of job ``j`` on a ``k``-slot slice, with the cell's
``mig_enabled`` speedup folded in), turning the per-job Python callables of
:mod:`repro.core.jobs` into one gather inside the scan.

:class:`BatchedResult` is the host-side mirror of the accumulator carry:
it converts back to the oracle's :class:`repro.core.metrics.SimResult` /
sweep result-dict vocabulary so downstream aggregation (ET tables, grids,
baselines) is backend-agnostic.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

# lint: waive[VG001] a fleet's arrival order and GPU of each job beside the one-GPU containers; bit-identity suites pin the one-GPU path
from repro import obs
from repro.core.jobs import Job
from repro.core.metrics import SimResult

__all__ = ["BatchedJobs", "BatchedResult", "PAD_MULTIPLE"]

#: job-axis padding multiple: every batch pads ``J`` up to this, so the
#: jitted scan recompiles only when workloads cross a 32-job boundary.
PAD_MULTIPLE = 32

_TARDY_EPS = 1e-9


@dataclasses.dataclass(frozen=True)
class BatchedJobs:
    """Rectangular ``(B, J)`` job arrays for a batch of rollouts.

    ``rate_by_slots`` has shape ``(B, J, K)`` with ``K = max_slots + 1``;
    level 0 is always 0.0 (an unassigned job depletes nothing).  ``valid``
    masks padding rows; ``num_jobs`` is the true per-rollout job count.
    """

    arrival: np.ndarray  # (B, J) float32, +inf padded
    deadline: np.ndarray  # (B, J) float32, +inf padded
    work: np.ndarray  # (B, J) float32, 0 padded
    rate_by_slots: np.ndarray  # (B, J, K) float32, 0 padded
    valid: np.ndarray  # (B, J) bool
    num_jobs: np.ndarray  # (B,) int32
    edf_order: np.ndarray  # (B, J) int32 job indices sorted by (deadline, id)

    @property
    def batch(self) -> int:
        """``B`` — number of rollouts advancing lock-step."""
        return int(self.arrival.shape[0])

    @property
    def padded_jobs(self) -> int:
        """``J`` — padded job capacity per rollout."""
        return int(self.arrival.shape[1])

    def by_arrival(self) -> np.ndarray:
        """``(B, J)`` int32: the EDF-layout index (:meth:`in_edf_order`) of
        each rollout's ``r``-th job in (arrival, id) order, padding last.

        A fleet's scan routes its arrivals in this order (docs/BATCHED_SIM.md
        §3); one host permutation per batch, like the layout itself.
        """
        B, J = self.edf_order.shape
        pos = np.empty_like(self.edf_order)
        np.put_along_axis(pos, self.edf_order,
                          np.broadcast_to(np.arange(J, dtype=np.int32), (B, J)), axis=1)
        order = np.argsort(self.arrival, axis=1, kind="stable")
        return np.take_along_axis(pos, order, axis=1)

    def in_edf_order(self) -> "BatchedJobs":
        """The EDF layout the scan step runs on: every per-job array
        permuted by ``edf_order``, so job index ``i`` of a rollout is its
        ``i``-th job in ``(deadline, id)`` order and ``edf_order`` becomes
        the identity.

        Deadlines are static, so one host permutation per batch replaces a
        per-step device gather; :func:`repro.core.batched.backend.result_of`
        maps completions back to this (caller) order.
        """
        with obs.span("batched.edf_layout"):
            order = self.edf_order
            B, J = order.shape

            def take(a: np.ndarray) -> np.ndarray:
                idx = order if a.ndim == 2 else order[:, :, None]
                return np.take_along_axis(a, idx, axis=1)

            return dataclasses.replace(
                self,
                arrival=take(self.arrival),
                deadline=take(self.deadline),
                work=take(self.work),
                rate_by_slots=take(self.rate_by_slots),
                valid=take(self.valid),
                edf_order=np.tile(np.arange(J, dtype=np.int32), (B, 1)),
            )

    @classmethod
    def from_job_lists(
        cls,
        job_lists: Sequence[Sequence[Job]],
        *,
        max_slots: int,
        mig_enabled: bool = True,
        pad_multiple: int = PAD_MULTIPLE,
        min_jobs: int = 1,
    ) -> "BatchedJobs":
        """Pad ``B`` ragged job lists into one rectangular container.

        Jobs must be fresh (``remaining == work``); the batched backend owns
        depletion state internally.  ``max_slots`` sizes the rate table's
        slot axis (use ``DeviceTables.max_slots``).  ``min_jobs`` floors the
        padded job axis — callers that run many batches through one compiled
        program (the RL trainer's round loop) pass the global maximum so
        every round shares one shape.
        """
        with obs.span("batched.pad") as counts:
            B = len(job_lists)
            if B == 0:
                raise ValueError("empty batch")
            longest = max((len(js) for js in job_lists), default=0)
            want = max(longest, int(min_jobs), 1)
            J = max(pad_multiple, -(-want // pad_multiple) * pad_multiple)
            K = max_slots + 1

            arrival = np.full((B, J), np.inf, dtype=np.float32)
            deadline = np.full((B, J), np.inf, dtype=np.float32)
            work = np.zeros((B, J), dtype=np.float32)
            rates = np.zeros((B, J, K), dtype=np.float32)
            valid = np.zeros((B, J), dtype=bool)
            num_jobs = np.zeros((B,), dtype=np.int32)

            # lint: waive[VG001] padding by column; tests/test_front_end_bit_identity.py pins every padded byte
            # a job's rates depend only on its curve and its no-MIG speedup:
            # one float64 row per distinct pair, computed as Job.rate_on does
            curve_of: Dict[Tuple[int, float], int] = {}
            firsts: List[Job] = []
            rows: List[Tuple[int, int, List[int]]] = []
            for b, jobs in enumerate(job_lists):
                n = num_jobs[b] = len(jobs)
                if n == 0:
                    continue
                w = np.fromiter((job.work for job in jobs), np.float64, n)
                left = np.fromiter((job.remaining for job in jobs), np.float64, n)
                run = np.flatnonzero(np.abs(left - w) > 1e-9)
                if run.size:
                    raise ValueError(
                        f"rollout {b} job {jobs[run[0]].job_id}: partially-run jobs "
                        "cannot enter a batched rollout"
                    )
                arrival[b, :n] = np.fromiter((job.arrival for job in jobs), np.float64, n)
                deadline[b, :n] = np.fromiter((job.deadline for job in jobs), np.float64, n)
                work[b, :n] = w
                valid[b, :n] = True
                index = []
                for job in jobs:
                    key = (id(job.elasticity), job.speedup_no_mig)
                    if key not in curve_of:
                        curve_of[key] = len(firsts)
                        firsts.append(job)
                    index.append(curve_of[key])
                rows.append((b, n, index))
            table = np.array(
                [[job.rate_on(float(k), mig_enabled) for k in range(1, K)] for job in firsts],
                dtype=np.float64,
            ).reshape(len(firsts), K - 1)
            for b, n, index in rows:
                rates[b, :n, 1:] = table[index]
            counts["jobs"], counts["curves"] = int(num_jobs.sum()), len(firsts)
            # deadlines are static, so EDF order is too: pre-sorting here turns
            # the per-step priority selection into a cumsum over a boolean mask
            # (stable sort keeps the oracle's (deadline, arrival, job_id)
            # tie-break, since job ids are arrival-ordered)
            edf_order = np.argsort(deadline, axis=1, kind="stable").astype(np.int32)
            return cls(
                arrival=arrival,
                deadline=deadline,
                work=work,
                rate_by_slots=rates,
                valid=valid,
                num_jobs=num_jobs,
                edf_order=edf_order,
            )


@dataclasses.dataclass(frozen=True)
class BatchedResult:
    """Per-rollout aggregates of one :func:`simulate_batch` call (numpy).

    Mirrors the oracle's :class:`SimResult` fields plus the side channels the
    sweep layer records (utilization histogram); ``completion`` keeps the
    exact per-job finish times (``+inf`` for padding rows).  A fleet's
    aggregates are sums over its ``devices`` GPUs (``util_histogram`` in
    GPU-minutes), as :func:`repro.fleet.aggregate_sim_results` sums them,
    and ``device`` holds the GPU each job was routed to.
    """

    energy_wh: np.ndarray  # (B,) float64
    tardiness_integral: np.ndarray  # (B,) float64
    busy_slot_minutes: np.ndarray  # (B,) float64
    preemptions: np.ndarray  # (B,) int64
    repartitions: np.ndarray  # (B,) int64
    completion: np.ndarray  # (B, J) float64, +inf on padding
    deadline: np.ndarray  # (B, J) float64
    valid: np.ndarray  # (B, J) bool
    num_jobs: np.ndarray  # (B,) int64
    makespan_min: np.ndarray  # (B,) float64
    util_histogram: np.ndarray  # (B, K) float64 minutes at each busy level
    device: Optional[np.ndarray] = None  # (B, J) int32 a fleet's GPU of each job
    devices: int = 1

    @property
    def batch(self) -> int:
        """``B`` — rollout count."""
        return int(self.energy_wh.shape[0])

    def dispatch_counts(self) -> np.ndarray:
        """``(B, devices)`` int64: jobs routed to each GPU of each rollout."""
        if self.device is None:
            return self.num_jobs[:, None].astype(np.int64)
        dev = np.where(self.valid, self.device, self.devices)
        return np.stack([np.bincount(row, minlength=self.devices + 1)[: self.devices]
                         for row in dev]).astype(np.int64)

    def _tardiness(self, b: int) -> np.ndarray:
        mask = self.valid[b]
        tardy = self.completion[b, mask] - self.deadline[b, mask]
        return np.maximum(tardy, 0.0)

    def to_sim_result(self, b: int) -> SimResult:
        """Rollout ``b`` as the oracle's :class:`SimResult`."""
        tardy = self._tardiness(b)
        n = int(self.num_jobs[b])
        total = float(tardy.sum())
        return SimResult(
            energy_wh=float(self.energy_wh[b]),
            avg_tardiness=total / max(n, 1),
            num_jobs=n,
            total_tardiness=total,
            preemptions=int(self.preemptions[b]),
            repartitions=int(self.repartitions[b]),
            max_tardiness=float(tardy.max()) if tardy.size else 0.0,
            deadline_misses=int((tardy > _TARDY_EPS).sum()),
            busy_slot_minutes=float(self.busy_slot_minutes[b]),
            extra={
                "makespan_min": float(self.makespan_min[b]),
                "tardiness_integral": float(self.tardiness_integral[b]),
            },
        )

    def to_sim_results(self) -> List[SimResult]:
        """All rollouts as :class:`SimResult`, batch order preserved."""
        return [self.to_sim_result(b) for b in range(self.batch)]

    def to_result_dicts(self) -> List[Dict[str, Any]]:
        """Sweep-layer result dicts (the ``run_cell`` vocabulary).

        ``config_trace`` is empty — like fleet cells, batched cells do not
        record the per-rollout switch trace (documented in docs/BATCHED_SIM.md).
        A fleet's dicts add ``dispatch_counts``, as the oracle's fleet cells.
        """
        with obs.span("batched.result"):
            out: List[Dict[str, Any]] = []
            routed = self.dispatch_counts() if self.devices > 1 else None
            for b, res in enumerate(self.to_sim_results()):
                hist = {
                    str(k): float(v)
                    for k, v in enumerate(self.util_histogram[b])
                    if v > 0.0
                }
                out.append(
                    {
                        "energy_wh": res.energy_wh,
                        "avg_tardiness": res.avg_tardiness,
                        "num_jobs": res.num_jobs,
                        "total_tardiness": res.total_tardiness,
                        "preemptions": res.preemptions,
                        "repartitions": res.repartitions,
                        "max_tardiness": res.max_tardiness,
                        "deadline_misses": res.deadline_misses,
                        "busy_slot_minutes": res.busy_slot_minutes,
                        "extra": dict(res.extra),
                        "util_histogram": hist,
                        "config_trace": [],
                    }
                )
                if routed is not None:
                    out[-1]["dispatch_counts"] = routed[b].tolist()
            return out
