"""Plain reference of one rollout on the batched backend's time grid.

The timed path runs many rollouts as one JAX program (float32, vmapped,
a two-level rank search and merged scatters).  This module is the same
semantics written out for one rollout in float64 numpy, step by step, as
docs/BATCHED_SIM.md sec. 3 states them; it imports nothing of the program
and builds its own device tables from the configuration file:

1. an elapsed repartition completes: jobs on surviving slices move to
   their slice's new index;
2. the DayNight target is taken up when no repartition is in flight and
   the rollout has not passed its stop time: jobs on slices that do not
   survive are preempted, the stall timer starts;
3. unless a repartition is in flight, the first in-system jobs in EDF order
   (deadline, then job id) take the slices fastest first; a job that leaves
   its slice counts a preemption;
4. the step advances ``dt``: running jobs deplete at their rate on their
   slice, a job finishing inside the step completes at its exact time, and
   the slice time it leaves runs the next waiting job for the rest of the
   step (one handoff per freed slice, fastest slice first);
5. tardiness, busy slot-minutes, energy at the step's mean busy level and
   the utilisation histogram are accrued up to the rollout's stop time,
   which is the first DayNight boundary after its last completion.

``order="fifo"`` replaces EDF by arrival order: the control that breaks
the configuration's EDF-FS guarantee.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List

import numpy as np

DAY_MIN = 24 * 60.0


@dataclass(frozen=True)
class Device:
    """A configuration's device model, flattened for stepping."""

    slots: Dict[int, List[int]]  # config id -> compute slots of each slice
    rank: Dict[int, List[int]]  # config id -> slice indices, fastest first
    survivor: Dict[tuple, Dict[int, int]]  # (old id, new id) -> {old index: new index}
    watts: List[float]  # by busy slots 0..max_slots
    max_slots: int
    penalty_min: float


def build_device(config: dict) -> Device:
    """Slot-placed slices, survivor maps and the power curve of ``config``."""
    dev = config["device"]
    align = {int(k): v for k, v in dev["alignment"].items()}
    placed = {}
    for cid, slices in dev["configs"].items():
        cursor, inst = 0, []
        for slots, mem in slices:
            a = align[slots]
            start = -(-cursor // a) * a
            inst.append((start, slots, mem))
            cursor = start + slots
        placed[int(cid)] = inst
    slots = {c: [s for _, s, _ in inst] for c, inst in placed.items()}
    rank = {c: sorted(range(len(s)), key=lambda i, s=s: -s[i]) for c, s in slots.items()}
    survivor = {}
    for a, ia in placed.items():
        for b, ib in placed.items():
            where = {key: j for j, key in enumerate(ib)}
            survivor[(a, b)] = {i: where[key] for i, key in enumerate(ia) if key in where}
    max_slots = max(st + s for inst in placed.values() for st, s, _ in inst)
    if max_slots != dev["total_slots"] or len(dev["watts_by_busy_slots"]) != max_slots + 1:
        raise ValueError("device table and power curve disagree on the slot count")
    return Device(slots, rank, survivor, list(dev["watts_by_busy_slots"]), max_slots,
                  float(dev["repartition_penalty_min"]))


def simulate(day, config: dict, device: Device, order: str = "edf") -> dict:
    """Run one rollout of ``day`` to its stop time; returns its accounts.

    The day's arrivals, deadlines, work and rates are taken at float32, the
    precision of the timed path's inputs; all arithmetic is float64.
    """
    f32 = np.float32
    arrival = day.arrival.astype(f32).astype(float)
    deadline = day.deadline.astype(f32).astype(float)
    remaining = day.work.astype(f32).astype(float)
    rates = day.rates.astype(f32).astype(float)
    n = arrival.shape[0]
    grid, pol = config["grid"], config["policy"]
    dt, teps, weps = grid["dt_min"], grid["time_eps_min"], grid["work_eps"]
    day_start, day_end = pol["day_start_min"], pol["day_end_min"]
    if order == "edf":
        prio = np.argsort(deadline.astype(f32), kind="stable")
    elif order == "fifo":
        prio = np.arange(n)
    else:
        raise ValueError(f"unknown order {order!r}")

    def target(t: float) -> int:
        tod = t % DAY_MIN
        return pol["day_config"] if day_start <= tod < day_end else pol["night_config"]

    completion = np.full(n, np.inf)
    zero = remaining <= weps
    completion[zero] = arrival[zero]
    cfg = pending = target(0.0)
    S = max(len(s) for s in device.slots.values())
    slice_job = [-1] * S
    stall = 0.0
    stop = math.inf if (~zero).any() else 0.0
    energy = tard = busy_total = 0.0
    pre = rep = 0
    hist = [0.0] * (device.max_slots + 1)
    watts = device.watts
    step = 0
    while True:
        t = step * dt
        if t > stop + teps:
            break
        step += 1
        # 1. an elapsed repartition completes
        if pending != cfg and stall <= teps:
            surv = device.survivor[(cfg, pending)]
            moved = [-1] * S
            for s, j in enumerate(slice_job):
                if j >= 0 and s in surv:
                    moved[surv[s]] = j
            slice_job, cfg = moved, pending
        # 2. the policy's target
        want = target(t)
        if pending == cfg and t <= stop + teps and want != cfg:
            surv = device.survivor[(cfg, want)]
            for s, j in enumerate(slice_job):
                if j >= 0 and s not in surv:
                    slice_job[s] = -1
                    pre += 1
            pending, stall = want, device.penalty_min
            rep += 1
        in_flight = pending != cfg
        # 3. EDF-FS: the first in-system jobs by priority, fastest slice first
        insys = (arrival <= t + teps) & (remaining > weps)
        ranked = device.rank[cfg]
        queue = prio[np.flatnonzero(insys[prio])[: 2 * S]].tolist()
        if not in_flight:
            new = [-1] * S
            for r, s in enumerate(ranked):
                if r < len(queue):
                    new[s] = queue[r]
            pre += sum(1 for s in range(S) if slice_job[s] >= 0 and new[s] != slice_job[s])
            slice_job = new
        # 4. advance dt
        slots_of = device.slots[cfg]
        writes, done_at = {}, {}
        busy = 0.0
        level = 0
        freed = {}
        tard += float(np.sum(np.maximum(t + dt - np.maximum(deadline[insys], t), 0.0)))
        for s, j in enumerate(slice_job):
            if j < 0:
                continue
            k = slots_of[s]
            level += k
            rate = rates[j, k]
            fin = remaining[j] / rate if rate > 0 else math.inf
            ran = min(fin, dt)
            busy += k * ran
            if fin <= dt + teps:
                done_at[j] = t + fin
                writes[j] = 0.0
                base = max(deadline[j], t)
                tard -= max(t + dt - base, 0.0) - max(t + fin - base, 0.0)
                slice_job[s] = -1
                if not in_flight:
                    freed[s] = dt - ran
            else:
                writes[j] = max(remaining[j] - rate * dt, 0.0)
        # 4b. the slice time a finished job leaves runs the next waiting job
        gives = [(s, freed[s]) for s in ranked if s in freed and freed[s] > teps]
        for q, (s, give) in enumerate(gives):
            if len(slots_of) + q >= len(queue):
                break
            j = queue[len(slots_of) + q]
            k = slots_of[s]
            rate = rates[j, k]
            fin = remaining[j] / rate if rate > 0 else math.inf
            busy += k * min(fin, give)
            if fin <= give + teps:
                end = t + dt - give + fin
                done_at[j] = end
                writes[j] = 0.0
                base = max(deadline[j], t)
                tard -= max(t + dt - base, 0.0) - max(end - base, 0.0)
            else:
                writes[j] = max(remaining[j] - rate * give, 0.0)
        for j, v in writes.items():
            remaining[j] = v
        for j, v in done_at.items():
            completion[j] = v
        # the rollout's stop time
        if math.isinf(stop) and not (remaining > weps).any():
            last = max([t, *done_at.values()])
            base = math.floor(last / DAY_MIN) * DAY_MIN
            stop = min(b for b in (base + day_start, base + day_end,
                                   base + DAY_MIN + day_start, base + DAY_MIN + day_end)
                       if b > last + teps)
        # 5. accounts over the part of the step before the stop time
        span = min(max(min(t + dt, stop) - t, 0.0), dt)
        busy_total += busy
        mean_busy = busy / span if span > teps else 0.0
        lo = min(max(int(math.floor(mean_busy)), 0), device.max_slots)
        hi = min(lo + 1, device.max_slots)
        frac = min(max(mean_busy - lo, 0.0), 1.0)
        energy += (watts[lo] * (1.0 - frac) + watts[hi] * frac) * span / 60.0
        hist[min(level, device.max_slots)] += span
        stall = max(stall - dt, 0.0)

    tardy = np.maximum(completion - deadline, 0.0)
    return {
        "num_jobs": n,
        "energy_wh": energy,
        "tardiness_integral": tard,
        "total_tardiness": float(tardy.sum()),
        "busy_slot_minutes": busy_total,
        "preemptions": pre,
        "repartitions": rep,
        "makespan_min": stop,
        "completion": completion,
    }
