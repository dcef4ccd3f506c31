"""Plain reference of one fleet rollout on the batched backend's time grid.

A fleet is ``devices`` identical GPUs fed by one job stream.  The timed
path steps every GPU of every rollout together as one JAX program
(float32, vmapped over the batch and the GPUs, a two-level rank search per
GPU and merged scatters); this module is the same semantics written out
for one rollout in float64 numpy, step by step, as docs/BATCHED_SIM.md
sec. 3 states them.  It imports nothing of the program, and builds each
GPU's tables with ``reference.build_device``:

0. dispatch: the jobs that arrived since the last step are routed one by
   one in (arrival, job id) order.  ``least-loaded`` picks the GPU with
   the smallest key (backlog / peak slots, index), where the backlog is
   the work left on the GPU's in-system jobs, those routed earlier in this
   step included; ``round-robin`` picks arrival rank mod ``devices``;
1. to 4b. on each GPU, over the jobs routed to it: the steps of
   ``reference.simulate`` (repartition completion, the DayNight target,
   EDF-FS placement fastest slice first, advance with exact completions,
   one handoff per freed slice);
5. the rollout's stop time is the DayNight boundary after the last
   completion anywhere in the fleet; energy, tardiness, busy slot-minutes,
   preemptions, repartitions and the utilisation histogram are sums over
   the GPUs up to it.

``dispatcher="round-robin"`` in place of the configuration's
``least-loaded`` is the control: the same fleet with the dispatch rule
broken.

A near-tie goes either way.  The timed path keeps each job's work left in
float32, rounded once a grid step, and sums the backlogs in float32, so
two GPUs whose backlogs lie within that rounding of each other may order
either way, and the first such flip would route the rest of the day apart
from the reference.  Given ``follow``, the GPU the timed path (or the
control) routed each job to, the reference takes that GPU where its
backlog lies above the least by more than 0 and by no more than
``TIE_ULPS`` float32 rounding units of each of the two backlogs' terms
(``near_tie``), and its own least-loaded GPU everywhere else.  Equal
backlogs are no near-tie: the lowest index takes them, as the dispatch
rule says, whatever ``follow`` says.  A GPU further from the least is a
routing fault, and shows as ``dispatch_differ``.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.chip.reference import DAY_MIN, Device

DISPATCHERS = ("least-loaded", "round-robin")

#: float32's spacing at 1: one rounding unit of a float32 number, relative
EPS32 = float(np.finfo(np.float32).eps)
#: float32 rounding units a backlog may be off by, per term of its sum: the
#: timed path rounds a job's work left once each grid step it runs, a walk
#: of up to about a thousand roundings for a training job on one slice
#: (32 = the square root of 1024), and rounds the sum once a term.  On a
#: TPU v5e, 768 server-days held three near-ties, at 0.04-0.17 of it.
TIE_ULPS = 32.0


def near_tie(backlog, terms, f: int, g: int) -> bool:
    """Whether GPU ``f``'s backlog lies above GPU ``g``'s, the least, within
    the float32 rounding of the two sums: ``TIE_ULPS`` units of each
    backlog for each of its ``terms``.  Equal backlogs are no near-tie."""
    gap = backlog[f] - backlog[g]
    return 0.0 < gap <= TIE_ULPS * EPS32 * (terms[f] * backlog[f] + terms[g] * backlog[g])


def simulate(day, config: dict, device: Device, dispatcher: str = "",
             follow=None, precision=None) -> dict:
    """Run one fleet rollout of ``day`` to its stop time; returns its accounts.

    The keys are those of ``reference.simulate``, plus ``device``: the GPU
    each job was routed to, and ``near_ties``: the arrivals routed as
    ``follow`` routed them.  ``follow`` resolves near-ties (see the
    module's docstring).  Inputs are taken at float32, the precision of the
    timed path's inputs; all arithmetic is float64.  ``precision`` (a numpy
    dtype below float32) takes the inputs at it instead and holds the work
    left, the completion times and the accounts in it, rounded at every
    step: the reading a limit must fail (``fleet_readings.py``).
    """
    dispatcher = dispatcher or config["dispatcher"]
    if dispatcher not in DISPATCHERS:
        raise ValueError(f"unknown dispatcher {dispatcher!r}")
    f32 = precision or np.float32
    held = precision or float

    def rnd(x: float) -> float:
        return float(held(x))

    arrival = day.arrival.astype(f32).astype(float)
    deadline = day.deadline.astype(f32).astype(float)
    remaining = day.work.astype(f32).astype(held)
    rates = day.rates.astype(f32).astype(float)
    n = arrival.shape[0]
    D = int(config["devices"])
    grid, pol = config["grid"], config["policy"]
    dt, teps, weps = grid["dt_min"], grid["time_eps_min"], grid["work_eps"]
    day_start, day_end = pol["day_start_min"], pol["day_end_min"]
    prio = np.argsort(deadline.astype(f32), kind="stable")
    by_arrival = np.argsort(arrival.astype(f32), kind="stable")
    arrival_sorted = arrival[by_arrival]

    def target(t: float) -> int:
        tod = t % DAY_MIN
        return pol["day_config"] if day_start <= tod < day_end else pol["night_config"]

    completion = np.full(n, np.inf, dtype=held)
    zero = remaining <= weps
    completion[zero] = arrival[zero]
    gpu = np.full(n, -1)
    routed = near_ties = 0
    cfg = [target(0.0)] * D
    pending = list(cfg)
    S = max(len(s) for s in device.slots.values())
    slice_job = [[-1] * S for _ in range(D)]
    stall = [0.0] * D
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
        # 0. dispatch this step's arrivals
        arrived = int(np.searchsorted(arrival_sorted, t + teps, side="right"))
        if arrived > routed:
            load = np.where(remaining > weps, remaining, 0.0)
            backlog = [float(load[gpu == d].sum()) for d in range(D)]
            terms = [int(np.count_nonzero(load[gpu == d])) for d in range(D)]
            for r in range(routed, arrived):
                j = by_arrival[r]
                if dispatcher == "round-robin":
                    g = r % D
                else:
                    g = min(range(D), key=lambda d: (backlog[d] / device.max_slots, d))
                    f = -1 if follow is None else int(follow[j])
                    if 0 <= f < D and near_tie(backlog, terms, f, g):
                        g = f
                        near_ties += 1
                backlog[g] += load[j]
                terms[g] += int(load[j] > 0)
                gpu[j] = g
            routed = arrived
        insys = (arrival <= t + teps) & (remaining > weps)
        # each GPU's first 2S in-system jobs in EDF order
        queues = [[] for _ in range(D)]
        for j in prio[np.flatnonzero(insys[prio])].tolist():
            q = queues[gpu[j]]
            if len(q) < 2 * S:
                q.append(j)
        writes, done_at = {}, {}
        tard += float(np.sum(np.maximum(t + dt - np.maximum(deadline[insys], t), 0.0)))
        busy = [0.0] * D
        level = [0] * D
        for d in range(D):
            sj = slice_job[d]
            # 1. an elapsed repartition completes
            if pending[d] != cfg[d] and stall[d] <= teps:
                surv = device.survivor[(cfg[d], pending[d])]
                moved = [-1] * S
                for s, j in enumerate(sj):
                    if j >= 0 and s in surv:
                        moved[surv[s]] = j
                sj, cfg[d] = moved, pending[d]
            # 2. the policy's target
            want = target(t)
            if pending[d] == cfg[d] and t <= stop + teps and want != cfg[d]:
                surv = device.survivor[(cfg[d], want)]
                for s, j in enumerate(sj):
                    if j >= 0 and s not in surv:
                        sj[s] = -1
                        pre += 1
                pending[d], stall[d] = want, device.penalty_min
                rep += 1
            in_flight = pending[d] != cfg[d]
            # 3. EDF-FS on this GPU's jobs, fastest slice first
            ranked = device.rank[cfg[d]]
            queue = queues[d]
            if not in_flight:
                new = [-1] * S
                for r, s in enumerate(ranked):
                    if r < len(queue):
                        new[s] = queue[r]
                pre += sum(1 for s in range(S) if sj[s] >= 0 and new[s] != sj[s])
                sj = new
            # 4. advance dt
            slots_of = device.slots[cfg[d]]
            freed = {}
            for s, j in enumerate(sj):
                if j < 0:
                    continue
                k = slots_of[s]
                level[d] += k
                rate = rates[j, k]
                fin = remaining[j] / rate if rate > 0 else math.inf
                ran = min(fin, dt)
                busy[d] += k * ran
                if fin <= dt + teps:
                    done_at[j] = t + fin
                    writes[j] = 0.0
                    base = max(deadline[j], t)
                    tard -= max(t + dt - base, 0.0) - max(t + fin - base, 0.0)
                    sj[s] = -1
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
                busy[d] += k * min(fin, give)
                if fin <= give + teps:
                    end = t + dt - give + fin
                    done_at[j] = end
                    writes[j] = 0.0
                    base = max(deadline[j], t)
                    tard -= max(t + dt - base, 0.0) - max(end - base, 0.0)
                else:
                    writes[j] = max(remaining[j] - rate * give, 0.0)
            slice_job[d] = sj
        for j, v in writes.items():
            remaining[j] = v
        for j, v in done_at.items():
            completion[j] = v
        # the rollout's stop time: every GPU drained
        if math.isinf(stop) and not (remaining > weps).any():
            last = max([t, *done_at.values()])
            base = math.floor(last / DAY_MIN) * DAY_MIN
            stop = min(b for b in (base + day_start, base + day_end,
                                   base + DAY_MIN + day_start, base + DAY_MIN + day_end)
                       if b > last + teps)
        # 5. accounts over the part of the step before the stop time
        span = min(max(min(t + dt, stop) - t, 0.0), dt)
        for d in range(D):
            busy_total += busy[d]
            mean_busy = busy[d] / span if span > teps else 0.0
            lo = min(max(int(math.floor(mean_busy)), 0), device.max_slots)
            hi = min(lo + 1, device.max_slots)
            frac = min(max(mean_busy - lo, 0.0), 1.0)
            energy += (watts[lo] * (1.0 - frac) + watts[hi] * frac) * span / 60.0
            hist[min(level[d], device.max_slots)] += span
            stall[d] = max(stall[d] - dt, 0.0)
        energy, tard, busy_total = rnd(energy), rnd(tard), rnd(busy_total)

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
        "device": gpu,
        "near_ties": near_ties,
    }
