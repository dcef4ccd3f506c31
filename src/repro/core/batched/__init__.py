"""Batched fixed-timestep simulation backend (vmap/scan rollouts).

The throughput half of the repo's two-backend contract (docs/BATCHED_SIM.md,
docs/ARCHITECTURE.md): the event-driven :class:`repro.core.engine.
SimulationEngine` remains the bit-exact oracle; this package advances many
(seed × scenario × config) rollouts lock-step as JAX arrays and reproduces
the oracle's ET/energy/tardiness aggregates within documented tolerances.

Public surface:

* :func:`build_tables` / :class:`DeviceTables` — the slot-placement model
  flattened to padded arrays (numpy, jax-free);
* :class:`BatchedJobs` / :class:`BatchedResult` — padded batch containers
  and the SimResult-compatible aggregates;
* :func:`compile_policy` / :class:`BatchedPolicy` — oracle policies
  compiled to per-rollout target arrays (static/nomig/daynight);
* :func:`simulate_batch` — run a batch to completion (jax imported here);
* :func:`agreement_failures` — the §4 agreement contract with the oracle.

Importing the package is jax-free; jax loads on the first simulated step.
"""

from repro.core.batched.agreement import agreement_failures
from repro.core.batched.backend import (
    DEFAULT_CHUNK_STEPS,
    DEFAULT_DT_MIN,
    RolloutState,
    simulate_batch,
)
from repro.core.batched.policies import (
    BatchedPolicy,
    UnsupportedPolicyError,
    compile_policy,
    held_policy,
)
from repro.core.batched.state import BatchedJobs, BatchedResult, PAD_MULTIPLE
from repro.core.batched.tables import DeviceTables, build_tables

__all__ = [
    "DEFAULT_CHUNK_STEPS",
    "DEFAULT_DT_MIN",
    "PAD_MULTIPLE",
    "BatchedJobs",
    "BatchedPolicy",
    "BatchedResult",
    "DeviceTables",
    "RolloutState",
    "UnsupportedPolicyError",
    "agreement_failures",
    "build_tables",
    "compile_policy",
    "held_policy",
    "simulate_batch",
]
