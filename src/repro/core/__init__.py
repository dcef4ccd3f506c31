"""The paper's primary contribution: energy-efficient MIG scheduling with
dynamic repartitioning (Lipe et al., CCGrid 2025), reproduced in JAX.

Layers:
* :mod:`repro.core.slices`     — Fig. 1 slice/partition model (12 configs)
* :mod:`repro.core.power`      — Fig. 3 saturating power curves
* :mod:`repro.core.jobs`       — jobs with linear/capped/sublinear elasticity
* :mod:`repro.core.workload`   — §V-A diurnal Poisson workload generator
* :mod:`repro.core.scenarios`  — named workload scenario registry
* :mod:`repro.core.metrics`    — §IV-A ET multi-objective metric
* :mod:`repro.core.schedulers` — §IV-C EDF-FS / EDF-SS / LLF / LALF
* :mod:`repro.core.engine`     — steppable event engine (step/inject/snapshot)
* :mod:`repro.core.simulator`  — event-driven preemptive simulator (numeric state + policies)
* :mod:`repro.core.rl`         — §IV-D DQN dynamic repartitioning (pure JAX)
"""

from repro.core.engine import (
    EngineEvent,
    EngineSnapshot,
    EventKind,
    SimSnapshot,
    SimulationEngine,
)

from repro.core.slices import MIG_CONFIGS, NUM_CONFIGS, Partition, SliceType, config
from repro.core.power import A100_250W, TPU_V5E_POD, PowerModel
from repro.core.jobs import Elasticity, ElasticityClass, Job, JobKind
from repro.core.workload import WorkloadSpec, generate_jobs, arrival_rate
from repro.core.scenarios import SCENARIOS, generate_scenario, scenario_names
from repro.core.metrics import SimResult, et_metric, et_scale_factor, et_table
from repro.core.schedulers import (
    SCHEDULERS,
    EDFFastestSlice,
    EDFSlowestSlice,
    LeastAverageLaxityFirst,
    LeastLaxityFirst,
    Scheduler,
    make_scheduler,
)
from repro.core.simulator import (
    DayNightPolicy,
    MIGSimulator,
    NoMIGPolicy,
    QueueHeuristicPolicy,
    StaticPolicy,
    REPARTITION_PENALTY_MIN,
    queue_heuristic_policy,
)

__all__ = [
    "EngineEvent",
    "EngineSnapshot",
    "EventKind",
    "SimSnapshot",
    "SimulationEngine",
    "MIG_CONFIGS",
    "NUM_CONFIGS",
    "Partition",
    "SliceType",
    "config",
    "A100_250W",
    "TPU_V5E_POD",
    "PowerModel",
    "Elasticity",
    "ElasticityClass",
    "Job",
    "JobKind",
    "WorkloadSpec",
    "generate_jobs",
    "arrival_rate",
    "SCENARIOS",
    "generate_scenario",
    "scenario_names",
    "SimResult",
    "et_metric",
    "et_scale_factor",
    "et_table",
    "SCHEDULERS",
    "EDFFastestSlice",
    "EDFSlowestSlice",
    "LeastAverageLaxityFirst",
    "LeastLaxityFirst",
    "Scheduler",
    "make_scheduler",
    "DayNightPolicy",
    "MIGSimulator",
    "NoMIGPolicy",
    "QueueHeuristicPolicy",
    "StaticPolicy",
    "REPARTITION_PENALTY_MIN",
    "queue_heuristic_policy",
]
