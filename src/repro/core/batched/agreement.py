"""The batched backend's agreement contract with the oracle (numpy, jax-free).

docs/BATCHED_SIM.md §4 fixes how far one batched rollout's aggregates may
drift from the oracle's run of the same jobs.  This module is that table and
its comparison, read by ``tests/test_batched.py`` and ``chip_smoke.py`` alike.
Tightening a tolerance requires re-measuring the calibration matrix;
loosening one requires naming the new divergence source in §4.  A fleet
rollout (``simulate_batch(..., devices=D)``) is held to the same table
against :class:`repro.fleet.FleetSimulator`'s aggregate, but for its
preemptions: dispatch on the grid (§4 D6) places a step's arrivals
together, where the oracle re-places at each, so D4's undercount grows.
"""

from __future__ import annotations

from typing import List

from repro.core.metrics import SimResult

__all__ = [
    "BUSY_ATOL_MIN",
    "BUSY_RTOL",
    "ENERGY_RTOL",
    "FLEET_PREEMPTIONS_RTOL",
    "PREEMPTIONS_FLOOR",
    "PREEMPTIONS_RTOL",
    "TARDINESS_ATOL_MIN",
    "TARDINESS_FLOOR",
    "TARDINESS_RTOL",
    "agreement_failures",
]

# measured at dt=0.5 (BATCHED_SIM.md §4)
ENERGY_RTOL = 0.03
TARDINESS_ATOL_MIN = 0.15  # minutes of avg tardiness, OR ...
TARDINESS_RTOL = 0.5  # ... relative to max(oracle, TARDINESS_FLOOR)
TARDINESS_FLOOR = 0.25
BUSY_RTOL = 0.025
BUSY_ATOL_MIN = 1.0  # slot-minutes: near-idle days compare absolutely
PREEMPTIONS_RTOL = 0.4  # relative to max(oracle, PREEMPTIONS_FLOOR)
PREEMPTIONS_FLOOR = 10.0
# measured at dt=0.5 on fleets of 3 and 8 GPUs, least-loaded, full days (§4, D6)
FLEET_PREEMPTIONS_RTOL = 0.5


def agreement_failures(batched: SimResult, oracle: SimResult, devices: int = 1) -> List[str]:
    """The §4 columns on which one batched rollout misses its oracle run.

    An empty list means the two agree.  Job and repartition counts must be
    exact; energy, tardiness, busy-slot minutes and preemptions are held to
    the tolerances above, a fleet's preemptions (``devices > 1``) to
    :data:`FLEET_PREEMPTIONS_RTOL`.
    """
    b, o = batched, oracle
    out: List[str] = []
    if b.num_jobs != o.num_jobs:
        out.append(f"num_jobs {b.num_jobs} != {o.num_jobs}")
    if b.repartitions != o.repartitions:
        out.append(f"repartitions {b.repartitions} != {o.repartitions}")
    if abs(b.energy_wh - o.energy_wh) > ENERGY_RTOL * abs(o.energy_wh):
        out.append(f"energy_wh {b.energy_wh} vs {o.energy_wh}")
    d_tard = abs(b.avg_tardiness - o.avg_tardiness)
    if d_tard > max(
        TARDINESS_ATOL_MIN,
        TARDINESS_RTOL * max(o.avg_tardiness, TARDINESS_FLOOR),
    ):
        out.append(f"avg_tardiness {b.avg_tardiness} vs {o.avg_tardiness}")
    d_busy = abs(b.busy_slot_minutes - o.busy_slot_minutes)
    if d_busy > max(BUSY_RTOL * abs(o.busy_slot_minutes), BUSY_ATOL_MIN):
        out.append(
            f"busy_slot_minutes {b.busy_slot_minutes} vs {o.busy_slot_minutes}"
        )
    pre_rtol = FLEET_PREEMPTIONS_RTOL if devices > 1 else PREEMPTIONS_RTOL
    if abs(b.preemptions - o.preemptions) > pre_rtol * max(
        o.preemptions, PREEMPTIONS_FLOOR
    ):
        out.append(f"preemptions {b.preemptions} vs {o.preemptions}")
    return out
