"""Cluster-day simulation: the paper's scheduler in charge of a TPU pod.

``python -m repro.launch.cluster_sim --policy dynamic --iterations 20``

Runs simulated days where diurnal (arch x shape) jobs from the assigned
architectures hit one 256-chip pod that EDF-SS schedules across the 12
partition profiles, with the repartitioning policy of your choice; energy
uses the TPU pod power curve.  ``--failures`` injects Poisson slice failures
(jobs requeue with checkpoint-gap work loss; the policy degrades to a
holed configuration until repair) — the paper's mechanism doubling as the
recovery path (DESIGN.md §5).
"""

from __future__ import annotations

import argparse
import math
from typing import Dict, List, Optional

import numpy as np

from repro.cluster.workload import ClusterWorkloadSpec, generate_cluster_jobs
from repro.core.metrics import SimResult, et_table
from repro.core.power import TPU_V5E_POD
from repro.core.schedulers import make_scheduler
from repro.core.simulator import (
    DayNightPolicy,
    MIGSimulator,
    RepartitionPolicy,
    StaticPolicy,
    queue_heuristic_policy,
)
from repro.distributed.fault_tolerance import FailureModel

__all__ = ["FailureAwarePolicy", "run_days", "main"]

# pod repartition penalty: rebuild meshes + restore job state from ckpt (min)
POD_REPARTITION_MIN = 0.5


class FailureAwarePolicy:
    """Wraps a policy with slice-failure handling.

    On failure: running jobs are requeued by the forced repartition, each
    charged the checkpoint-gap work loss; the pod runs a holed configuration
    (config 5: 6/7 slots) until repair.
    """

    DEGRADED_CONFIG = 5

    def __init__(self, inner: RepartitionPolicy, failures, model: FailureModel):
        self.inner = inner
        self.initial_config = inner.initial_config
        self.events = list(failures)  # [(t_fail, slice_idx, t_repair)]
        self.outages: List = []
        self.recoveries = 0
        self.lost_work_min = 0.0

    def _outage_at(self, t: float) -> bool:
        return any(f <= t < r for f, _, r in self.events)

    def decide(self, t, sim):
        if self._outage_at(t):
            if sim.partition.config_id != self.DEGRADED_CONFIG:
                # charge checkpoint-gap loss to every running job
                for jid in list(sim.assignment):
                    job = sim.active[jid]
                    lost = min(10.0, job.work - job.remaining)
                    lost = max(lost, 0.0) * 0.5  # expected gap/2
                    job.remaining = min(job.remaining + lost, job.work)
                    self.lost_work_min += lost
                self.recoveries += 1
                return self.DEGRADED_CONFIG
            return None
        return self.inner.decide(t, sim)

    def next_timer(self, t):
        bounds = [x for f, _, r in self.events for x in (f, r) if x > t + 1e-9]
        inner = self.inner.next_timer(t)
        if inner is not None:
            bounds.append(inner)
        return min(bounds) if bounds else None


def run_days(
    policy_factory,
    iterations: int = 10,
    spec: Optional[ClusterWorkloadSpec] = None,
    scheduler: str = "EDF-SS",
    failures: Optional[FailureModel] = None,
    seed: int = 0,
) -> List[SimResult]:
    spec = spec or ClusterWorkloadSpec()
    sim = MIGSimulator(
        make_scheduler(scheduler),
        power_model=TPU_V5E_POD,
        repartition_penalty_min=POD_REPARTITION_MIN,
    )
    out: List[SimResult] = []
    for it in range(iterations):
        jobs = generate_cluster_jobs(spec, seed=seed + it)
        policy = policy_factory()
        if failures is not None:
            fl = failures.sample_failures(7, spec.horizon_min)
            policy = FailureAwarePolicy(policy, fl, failures)
        out.append(sim.run(jobs, policy=policy))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--policy",
        default="heuristic",
        choices=["static", "daynight", "heuristic", "dynamic"],
    )
    ap.add_argument("--iterations", type=int, default=10)
    ap.add_argument("--failures", action="store_true")
    ap.add_argument("--dqn-params", default="artifacts/dqn_params.npz")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    def factory():
        if args.policy == "static":
            return StaticPolicy(3)
        if args.policy == "daynight":
            return DayNightPolicy()
        if args.policy == "heuristic":
            return queue_heuristic_policy()
        from repro.core.rl import DQNConfig, DQNLearner, greedy_policy
        from repro.core.rl.env import FEATURE_DIM

        learner = DQNLearner(DQNConfig(state_dim=FEATURE_DIM))
        learner.load(args.dqn_params)
        return greedy_policy(learner)

    fm = FailureModel(mtbf_minutes=2 * 24 * 60.0) if args.failures else None
    results = run_days(factory, iterations=args.iterations, failures=fm, seed=args.seed)
    n = len(results)
    print(
        f"policy={args.policy} days={n} "
        f"energy={sum(r.energy_wh for r in results)/n/1000.0:.1f} kWh/day "
        f"avg_tardiness={sum(r.avg_tardiness for r in results)/n:.3f} min "
        f"repartitions={sum(r.repartitions for r in results)/n:.1f}/day "
        f"misses={sum(r.deadline_misses for r in results)/n:.1f}/day"
    )


if __name__ == "__main__":
    main()
