"""Fixed-timestep batched rollouts: ``vmap`` over the batch, ``scan`` over time.

This is the throughput backend of the two-backend contract
(docs/BATCHED_SIM.md): the event-driven :class:`repro.core.engine.
SimulationEngine` stays the bit-exact oracle, while this module advances many
independent rollouts lock-step on a ``dt_min`` time grid as one JAX program.

Per step (see docs/BATCHED_SIM.md §3 for the full semantics):

0. a fleet of ``D`` GPUs routes the step's arrivals, one at a time, to
   the GPU the dispatcher picks (one GPU: every job is on device 0);
1. an elapsed repartition completes (survivors remapped via the
   ``old_to_new`` table, pending config installed);
2. the compiled policy may start a repartition — jobs on non-surviving
   slices are preempted, the §IV-D-3 stall timer starts;
3. EDF-FS reassigns eligible jobs to fastest-first slices (frozen while a
   repartition is in flight), preemptions counted by diffing assignments;
4. the step advances ``dt``: work depletes, completions land at their exact
   sub-step time, tardiness/energy/busy accumulators integrate over the
   step (energy uses the power curve at the step's time-averaged busy).

A rollout's accounting stops at its ``stop_time`` — the oracle's end-of-run
point (last completion for static policies; the one post-drain boundary
timer a DayNight run still fires).  The host driver re-invokes one jitted
chunk until every rollout has passed its stop time, so wall-clock cost
scales with the slowest rollout, not a global horizon guess.  A fleet's
rollout stops once every GPU is drained: its accounts are sums over the
GPUs up to that one stop time.

Numerics are float32 throughout (JAX CPU default); the documented
oracle-agreement tolerances in docs/BATCHED_SIM.md §4 absorb both the ``dt``
discretization and float32 accumulation.
"""

from __future__ import annotations

import functools
import re
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np

# lint: waive[VG001] a fleet device axis beside the one-GPU step, whose program is unchanged; bit-identity and fleet-agreement suites pin both
from repro import obs
from repro.core.batched.policies import BatchedPolicy
from repro.core.batched.state import BatchedJobs, BatchedResult
from repro.core.batched.tables import DeviceTables, build_tables
from repro.core.simulator import REPARTITION_MODES

__all__ = [
    "DEFAULT_DT_MIN",
    "DEFAULT_CHUNK_STEPS",
    "DISPATCHERS",
    "STEP_PHASES",
    "RolloutState",
    "chunk_op_scopes",
    "device_constants",
    "init_state",
    "least_loaded",
    "make_step_fn",
    "op_phases",
    "run_steps",
    "simulate_batch",
    "result_of",
]

#: default time-grid resolution (minutes). Must divide 60 so the DayNight
#: boundaries (multiples of 60 min) land exactly on grid points.
DEFAULT_DT_MIN = 0.5

#: steps per jitted scan chunk; the host loop re-invokes the same compiled
#: chunk until every rollout passes its stop time.
DEFAULT_CHUNK_STEPS = 512

_DAY = 24 * 60.0
# float32 grid: time comparisons tolerate ~1e-6 min, work ~1e-6 1g-minutes
_T_EPS = 1e-6
_W_EPS = 1e-6
#: job-axis block size for the two-level EDF rank search; J must be a
#: multiple of this (BatchedJobs pads to PAD_MULTIPLE == _BLOCK).
_BLOCK = 32

#: the phases of one step, in order, each a ``jax.named_scope`` of
#: :func:`make_step_fn`: 0 (a fleet's routing of arrivals; no op on one
#: GPU), 1, 2, 3 (the EDF rank search and reassignment), 4, 4b without its
#: write-back, the merged write-back scatters, the rollout end detection,
#: and 5.  Scopes change op metadata only.
STEP_PHASES = ("dispatch", "repartition", "policy", "edf_rank", "advance", "handoff",
               "writeback", "end_detect", "accounting")

#: the fleet dispatchers the scan implements, by their
#: :mod:`repro.fleet.dispatch` names: the smallest backlog over peak slots
#: (ties to the lower index), and arrival rank modulo the fleet size
DISPATCHERS = ("least-loaded", "round-robin")


class RolloutState(NamedTuple):
    """The scan carry: every mutable per-rollout quantity, batch-leading.

    ``cfg``/``pending`` are dense config indices (``pending != cfg`` means a
    repartition is in flight); ``stop_time`` is ``+inf`` until the rollout's
    accounting endpoint is known.  Accumulators mirror the oracle's
    :class:`~repro.core.simulator.MIGSimulator` counters.  A fleet of ``D``
    GPUs adds a device axis after ``B`` to ``slice_job``, ``cfg``,
    ``pending`` and ``stall_left``, sums every accumulator over its GPUs,
    and carries ``device``; one GPU has no device axis and ``device`` None.
    """

    remaining: Any  # (B, J) f32 work left
    completion: Any  # (B, J) f32, +inf until completed
    slice_job: Any  # (B, S) i32 job index running on each slice, -1 = idle
    cfg: Any  # (B,) i32 dense config index
    pending: Any  # (B,) i32 repartition target (== cfg when idle)
    stall_left: Any  # (B,) f32 minutes of stall remaining
    stop_time: Any  # (B,) f32 accounting endpoint, +inf while running
    energy_wh: Any  # (B,) f32
    tardiness_integral: Any  # (B,) f32
    busy_slot_minutes: Any  # (B,) f32
    preemptions: Any  # (B,) i32
    repartitions: Any  # (B,) i32
    util_hist: Any  # (B, K) f32 minutes at each integer busy level
    device: Any = None  # (B, J) i32 a fleet's GPU of each job, -1 until it arrives


def device_constants(
    tables: DeviceTables, repartition_mode: str = "partial"
) -> Dict[str, Any]:
    """Device-side copies of the tables one ``simulate_batch`` run needs.

    Drain mode degenerates the survivor table to all-(−1): every slice is
    destroyed on any switch, exactly the legacy full-drain model.
    """
    import jax.numpy as jnp

    if repartition_mode not in REPARTITION_MODES:
        raise ValueError(
            f"unknown repartition_mode {repartition_mode!r}; valid: "
            f"{REPARTITION_MODES}"
        )
    o2n = tables.old_to_new
    if repartition_mode == "drain":
        o2n = np.full_like(o2n, -1)
    return {
        "slice_slots": jnp.asarray(tables.slice_slots),
        "slice_rank": jnp.asarray(tables.slice_rank),
        "num_slices": jnp.asarray(tables.num_slices),
        "old_to_new": jnp.asarray(o2n),
        "watts": jnp.asarray(tables.watts_by_busy),
    }


def init_state(jobs: BatchedJobs, initial_idx: np.ndarray, devices: int = 1) -> RolloutState:
    """Fresh carry at ``t = 0`` with per-rollout initial config indices.

    Rollouts with no jobs (or only zero-work jobs) are already "finished":
    their ``stop_time`` is 0 and zero-work jobs complete at their arrival,
    matching the oracle's immediate-completion sweep.  ``devices > 1``
    starts every GPU of a fleet in the rollout's initial configuration.
    """
    import jax.numpy as jnp

    B, J = jobs.arrival.shape
    K = jobs.rate_by_slots.shape[2]
    S = K - 1  # DeviceTables pads slices to max_slots
    zero_work = jobs.valid & (jobs.work <= _W_EPS)
    completion0 = np.where(zero_work, jobs.arrival, np.inf).astype(np.float32)
    has_work = (jobs.valid & (jobs.work > _W_EPS)).any(axis=1)
    stop0 = np.where(has_work, np.inf, 0.0).astype(np.float32)
    init = np.asarray(initial_idx, dtype=np.int32)
    if init.shape != (B,):
        raise ValueError(f"initial_idx shape {init.shape} != ({B},)")
    f32 = jnp.float32
    lanes = (B,) if devices == 1 else (B, devices)
    init = np.broadcast_to(init[:, None], lanes) if devices > 1 else init
    return RolloutState(
        remaining=jnp.asarray(jobs.work, dtype=f32),
        completion=jnp.asarray(completion0),
        slice_job=jnp.full((*lanes, S), -1, dtype=jnp.int32),
        cfg=jnp.asarray(init),
        pending=jnp.asarray(init),
        stall_left=jnp.zeros(lanes, dtype=f32),
        stop_time=jnp.asarray(stop0),
        energy_wh=jnp.zeros((B,), dtype=f32),
        tardiness_integral=jnp.zeros((B,), dtype=f32),
        busy_slot_minutes=jnp.zeros((B,), dtype=f32),
        preemptions=jnp.zeros((B,), dtype=jnp.int32),
        repartitions=jnp.zeros((B,), dtype=jnp.int32),
        util_hist=jnp.zeros((B, K), dtype=f32),
        device=jnp.full((B, J), -1, dtype=jnp.int32) if devices > 1 else None,
    )


def least_loaded(backlog, peak_slots: int):
    """The GPU ``least-loaded`` picks: the smallest key (backlog / peak
    slots, index), as :class:`repro.fleet.dispatch.LeastLoadedDispatcher`."""
    import jax.numpy as jnp

    return jnp.argmin(backlog / jnp.float32(peak_slots)).astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def make_step_fn(kind: str, dt: float, penalty: float,
                 day_start: float, day_end: float,
                 devices: int = 1, dispatcher: str = "least-loaded"):
    """Build (and cache) the per-(rollout, step) physics function.

    This is the single source of the batched step semantics: both the
    simulation chunk below and the fused RL training scan
    (:mod:`repro.core.rl.batched_train`) vmap exactly this function, so an
    agent trains against the very physics its rollouts are evaluated on.
    The cache key mirrors :func:`_chunk_fn` minus the step count.

    ``devices`` is the static device axis ``D`` of a fleet of identical
    GPUs fed by one job stream, and ``dispatcher`` (one of
    :data:`DISPATCHERS`) routes each arrival to one of them in the
    ``dispatch`` phase.  Phases 1 to 4b run per GPU on that GPU's jobs;
    the write-back, the end detection and the accounting run over the
    fleet.  At ``devices == 1`` the carry has no device axis, ``dispatch``
    leaves no op (every job is on device 0) and the program is the
    single-GPU step.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    if devices < 1:
        raise ValueError(f"devices must be >= 1, got {devices}")
    if dispatcher not in DISPATCHERS:
        raise ValueError(f"unknown dispatcher {dispatcher!r}; the scan runs {DISPATCHERS}")
    fleet = devices > 1
    (dispatch, repartition, policy, edf_rank, advance, handoff, writeback, end_detect,
     accounting) = STEP_PHASES
    i32, f32 = jnp.int32, jnp.float32

    def total(x):
        # a fleet's per-GPU terms summed; one GPU's term as it is
        return jnp.sum(x, axis=0) if fleet else x

    def route(device, t, arrival, remaining, valid, by_arrival, max_slots):
        # the step's arrivals in (arrival, id) order, each to one GPU; an
        # arrival sees the work left on every GPU, the jobs routed before
        # it in this step included.  Routed jobs are a prefix of the
        # arrival order, so this step's are ranks r0 .. r0 + n_new - 1.
        J = arrival.shape[0]
        arrived = (arrival <= t + _T_EPS) & valid
        n_new = jnp.sum(arrived & (device < 0), dtype=i32)
        r0 = jnp.sum(arrived, dtype=i32) - n_new
        load = jnp.where(remaining > _W_EPS, remaining, 0.0)
        ids = jnp.arange(devices, dtype=i32)
        if dispatcher == "least-loaded":
            backlog = jnp.sum(jnp.where(device[None, :] == ids[:, None], load[None, :], 0.0),
                              axis=1)
        else:
            backlog = jnp.zeros((devices,), f32)

        def one(state):
            k, device, backlog = state
            r = r0 + k
            j = by_arrival[jnp.clip(r, 0, J - 1)]
            if dispatcher == "round-robin":
                g = jnp.mod(r, devices)
            else:
                g = least_loaded(backlog, max_slots)
            backlog = backlog + jnp.where(ids == g, load[j], 0.0)
            return k + 1, device.at[j].set(g), backlog

        _, device, _ = lax.while_loop(lambda s: s[0] < n_new, one,
                                      (jnp.int32(0), device, backlog))
        return device

    def step_one(carry, t, arrival, deadline, rates, valid,
                 primary, secondary,
                 slice_slots, slice_rank, num_slices, o2n, watts, by_arrival=None):
        # one rollout, one step.  All per-job state is (J,) in the EDF layout
        # (job index i is the i-th job by (deadline, id); BatchedJobs.
        # in_edf_order); everything about the <= S running jobs of a GPU
        # lives in (S,) lanes keyed by slice index (``slice_job``), so the
        # only O(J) work per step is a handful of fused elementwise ops plus
        # one cumsum per GPU — no sorts and no permutation: EDF priority is
        # the index order.  A fleet adds a leading device axis to the lanes
        # and routes each job once, on arrival (``device``).
        (remaining, completion, slice_job, cfg, pending, stall_left,
         stop_time, energy, tard, busy_min, pre, rep, hist, device) = carry
        S = slice_slots.shape[1]
        J = remaining.shape[0]
        max_slots = watts.shape[0] - 1

        # -- 0. dispatch (a fleet only) -----------------------------------
        if fleet:
            with jax.named_scope(dispatch):
                device = route(device, t, arrival, remaining, valid, by_arrival, max_slots)

        def gpu_front(slice_job, cfg, pending, stall_left, pre, rep, tard, d):
            # phases 1 to 4b of one GPU; ``d`` is its index in a fleet
            # -- 1. an elapsed repartition completes --------------------
            with jax.named_scope(repartition):
                in_flight = pending != cfg
                finish = in_flight & (stall_left <= _T_EPS)
                surv = o2n[cfg, pending]  # (S,) old->new survivor indices
                occ = slice_job >= 0
                keep = finish & occ & (surv >= 0)
                remapped = jnp.full((S,), -1, i32).at[
                    jnp.where(keep, surv, S)
                ].set(jnp.where(keep, slice_job, -1), mode="drop")
                slice_job = jnp.where(finish, remapped, slice_job)
                cfg = jnp.where(finish, pending, cfg)

            # -- 2. policy decision (never mid-flight, never past stop) -
            with jax.named_scope(policy):
                in_flight = pending != cfg
                if kind == "daynight":
                    tod = jnp.mod(t, _DAY)
                    is_day = (tod >= day_start) & (tod < day_end)
                    target = jnp.where(is_day, primary, secondary)
                else:
                    target = primary
                want = (~in_flight) & (t <= stop_time + _T_EPS) & (target != cfg)
                surv_t = o2n[cfg, target]  # (S,)
                kill = want & (slice_job >= 0) & (surv_t < 0)
                pre = pre + jnp.sum(kill).astype(i32)
                slice_job = jnp.where(kill, -1, slice_job)
                pending = jnp.where(want, target, pending)
                stall_left = jnp.where(want, jnp.float32(penalty), stall_left)
                rep = rep + want.astype(i32)
                in_flight = pending != cfg

            # -- 3. EDF-FS reassignment (frozen while repartitioning) ---
            with jax.named_scope(edf_rank):
                # first 2S in-system jobs in EDF order: the job axis is already
                # in EDF order, so find the first 2S set bits of the in-system
                # mask with a two-level rank search — per-block popcounts, a
                # short cumsum over blocks, and an intra-block scan only for the
                # <= 2S hit blocks.  (A full-J cumsum or an O(J)-update scatter
                # here dominates the whole step on CPU XLA.)
                insys = (arrival <= t + _T_EPS) & (remaining > _W_EPS) & valid
                if d is not None:
                    insys = insys & (device == d)
                NB = J // _BLOCK
                mb = insys.reshape(NB, _BLOCK)
                bc = jnp.cumsum(jnp.sum(mb, axis=1, dtype=i32))  # (NB,)
                ranks = jnp.arange(1, 2 * S + 1, dtype=i32)
                blk = jnp.searchsorted(bc, ranks)  # first block with cum >= rank
                blkc = jnp.clip(blk, 0, NB - 1)
                prev = jnp.where(blk > 0, bc[jnp.maximum(blk - 1, 0)], 0)
                sub = mb[blkc]  # (2S, BLOCK)
                sc = jnp.cumsum(sub.astype(i32), axis=1)
                need = (ranks - prev)[:, None]
                off = jnp.argmax(sub & (sc == need), axis=1)
                pos = blkc * _BLOCK + off
                cand = jnp.where(blk < NB, pos, J)
                ranked = slice_rank[cfg]  # (S,) slice ids fastest-first, -1 padded
                rv = (ranked >= 0) & (cand[:S] < J)
                proposed = jnp.full((S,), -1, i32).at[
                    jnp.where(rv, ranked, S)
                ].set(jnp.where(rv, cand[:S], -1), mode="drop")
                new_sj = jnp.where(in_flight, slice_job, proposed)
                moved = (slice_job >= 0) & (new_sj != slice_job) & (~in_flight)
                pre = pre + jnp.sum(moved).astype(i32)
                slice_job = new_sj

            # -- 4. advance dt ------------------------------------------
            with jax.named_scope(advance):
                run = slice_job >= 0
                sjc = jnp.clip(slice_job, 0, J - 1)
                slots_of = slice_slots[cfg]  # (S,)
                slot_s = jnp.where(run, slots_of, 0)
                rem_s = remaining[sjc]
                rate_s = rates[sjc, slot_s]
                fin = jnp.where(run & (rate_s > 0),
                                rem_s / jnp.maximum(rate_s, 1e-12), jnp.inf)
                run_time = jnp.where(run, jnp.minimum(fin, dt), 0.0)
                done = run & (fin <= dt + _T_EPS)
                comp_t = t + fin
                new_rem_s = jnp.where(done, 0.0,
                                      jnp.maximum(rem_s - rate_s * dt, 0.0))
                # (J,)-array writes are deferred and merged with the handoff's
                # into one scatter per array — scatters carry a large fixed cost
                busy_minutes = jnp.sum(slot_s * run_time)

                # tardiness: each in-system job accrues overlap of its
                # busy/waiting span with [deadline, inf); jobs completing
                # mid-step get the overshoot past their exact completion
                # refunded (S-space)
                tard = tard + jnp.sum(jnp.where(
                    insys, jnp.maximum(t + dt - jnp.maximum(deadline, t), 0.0), 0.0
                ))
                base_s = jnp.maximum(deadline[sjc], t)
                over = jnp.where(done,
                                 jnp.maximum(t + dt - base_s, 0.0)
                                 - jnp.maximum(comp_t - base_s, 0.0), 0.0)
                tard = tard - jnp.sum(over)
                held = slice_job  # lane->job ids before done lanes are cleared
                slice_job = jnp.where(done, -1, slice_job)

            # -- 4b. same-step handoff of freed capacity ----------------
            with jax.named_scope(handoff):
                # the oracle reassigns at the completion event; without this
                # pass a deep queue on few slices loses up to dt per handoff
                # and the error compounds down the queue.  One round per step
                # (no cascading): the r-th freed slice (fastest-first) runs
                # the r-th waiting job (EDF-first: candidates num_slices.. of
                # the buffer built above).
                leftover = jnp.where(done & (~in_flight), dt - run_time, 0.0)
                nsl = num_slices[cfg]
                fr = jnp.where(ranked >= 0,
                               leftover[jnp.clip(ranked, 0, S - 1)], 0.0)
                has = fr > _T_EPS
                hrk = jnp.cumsum(has.astype(i32))
                hpos = jnp.where(has, hrk - 1, S)
                fslice = jnp.full((S,), -1, i32).at[hpos].set(
                    jnp.where(has, ranked, -1), mode="drop")
                fgive = jnp.zeros((S,), jnp.float32).at[hpos].set(
                    jnp.where(has, fr, 0.0), mode="drop")
                wjob = cand[jnp.clip(nsl + jnp.arange(S, dtype=i32), 0, 2 * S - 1)]
                wok = (fslice >= 0) & (wjob < J)
                wjc = jnp.clip(wjob, 0, J - 1)
                w_rem = remaining[wjc]  # they were waiting: untouched by phase 4
                slot_w = slots_of[jnp.clip(fslice, 0, S - 1)]
                rate_w = rates[wjc, jnp.where(wok, slot_w, 0)]
                fin_w = jnp.where(wok & (rate_w > 0),
                                  w_rem / jnp.maximum(rate_w, 1e-12), jnp.inf)
                h_done = wok & (fin_w <= fgive + _T_EPS)
                tc = (t + dt - fgive) + fin_w
                new_wrem = jnp.where(h_done, 0.0,
                                     jnp.maximum(w_rem - rate_w * fgive, 0.0))
            return dict(
                slice_job=slice_job, cfg=cfg, pending=pending, stall_left=stall_left,
                pre=pre, rep=rep, tard=tard, busy_minutes=busy_minutes, run=run,
                held=held, new_rem_s=new_rem_s, done=done, comp_t=comp_t, slot_s=slot_s,
                wok=wok, wjob=wjob, wjc=wjc, new_wrem=new_wrem, h_done=h_done, tc=tc,
                slot_w=slot_w, fin_w=fin_w, fgive=fgive,
            )

        def gpu_back(busy_minutes, tard, wok, slot_w, fin_w, fgive, wjc, h_done, tc):
            # the handoff's busy time and tardiness refund, per GPU
            with jax.named_scope(handoff):
                busy_minutes = busy_minutes + jnp.sum(jnp.where(
                    wok, slot_w * jnp.minimum(fin_w, fgive), 0.0))
                # it accrued tardiness as waiting-to-step-end; completing at
                # tc refunds the overshoot
                base_w = jnp.maximum(deadline[wjc], t)
                refund = (jnp.maximum(t + dt - base_w, 0.0)
                          - jnp.maximum(tc - base_w, 0.0))
                tard = tard - jnp.sum(jnp.where(h_done, refund, 0.0))
            return busy_minutes, tard

        if fleet:  # per-GPU partial sums start at 0 and are added below
            g = jax.vmap(gpu_front, in_axes=(0, 0, 0, 0, None, None, None, 0))(
                slice_job, cfg, pending, stall_left, 0, 0, 0.0, np.arange(devices))
        else:
            g = gpu_front(slice_job, cfg, pending, stall_left, pre, rep, tard, None)
        slice_job, cfg, pending, stall_left = (g["slice_job"], g["cfg"], g["pending"],
                                               g["stall_left"])
        run, held, done, comp_t, wok, wjob, h_done, tc = (
            g["run"], g["held"], g["done"], g["comp_t"], g["wok"], g["wjob"], g["h_done"],
            g["tc"])

        with jax.named_scope(writeback):
            # merged write-back: running jobs (phase 4) and handoff jobs touch
            # disjoint index sets (as do the GPUs of a fleet), so one scatter
            # per array suffices
            rem_idx = jnp.concatenate([jnp.where(run, held, J).reshape(-1),
                                       jnp.where(wok, wjob, J).reshape(-1)])
            remaining = remaining.at[rem_idx].set(
                jnp.concatenate([g["new_rem_s"].reshape(-1), g["new_wrem"].reshape(-1)]),
                mode="drop")
            comp_idx = jnp.concatenate([jnp.where(done, held, J).reshape(-1),
                                        jnp.where(h_done, wjob, J).reshape(-1)])
            completion = completion.at[comp_idx].set(
                jnp.concatenate([comp_t.reshape(-1), tc.reshape(-1)]), mode="drop")
        back = jax.vmap(gpu_back) if fleet else gpu_back
        busy_minutes, g_tard = back(g["busy_minutes"], g["tard"], wok, g["slot_w"],
                                    g["fin_w"], g["fgive"], g["wjc"], h_done, tc)
        if fleet:
            with jax.named_scope(accounting):
                pre = pre + jnp.sum(g["pre"])
                rep = rep + jnp.sum(g["rep"])
                tard = tard + jnp.sum(g_tard)
        else:
            pre, rep, tard = g["pre"], g["rep"], g_tard

        # -- rollout end detection (over the fleet) ---------------------
        with jax.named_scope(end_detect):
            all_done = ~jnp.any(valid & (remaining > _W_EPS))
            finishes = all_done & (~jnp.isfinite(stop_time))
            e = jnp.maximum(jnp.maximum(
                jnp.max(jnp.where(done, comp_t, -jnp.inf)),
                jnp.max(jnp.where(h_done, tc, -jnp.inf))), t)
            if kind == "daynight":
                # the oracle still fires the one pending boundary timer after
                # the last completion (idle until the boundary, then switches)
                base = jnp.floor(e / _DAY) * _DAY
                cands = jnp.stack([
                    base + day_start, base + day_end,
                    base + _DAY + day_start, base + _DAY + day_end,
                ])
                end_stop = jnp.min(jnp.where(cands > e + _T_EPS, cands, jnp.inf))
            else:
                end_stop = e
            stop_time = jnp.where(finishes, end_stop, stop_time)

        # -- 5. energy / busy / histogram over the accounted span -------
        with jax.named_scope(accounting):
            span = jnp.clip(jnp.minimum(t + dt, stop_time) - t, 0.0, dt)
            busy_min = busy_min + total(busy_minutes)
            avg_busy = jnp.where(
                span > _T_EPS, busy_minutes / jnp.maximum(span, _T_EPS), 0.0
            )
            lo = jnp.clip(jnp.floor(avg_busy).astype(i32), 0, max_slots)
            hi = jnp.clip(lo + 1, 0, max_slots)
            frac = jnp.clip(avg_busy - lo.astype(jnp.float32), 0.0, 1.0)
            watts_now = watts[lo] * (1.0 - frac) + watts[hi] * frac
            energy = energy + total(watts_now * span / 60.0)
            level = jnp.clip(jnp.sum(g["slot_s"], axis=-1), 0, max_slots)
            hist = hist.at[level].add(span)

            stall_left = jnp.maximum(stall_left - dt, 0.0)
        return RolloutState(
            remaining, completion, slice_job, cfg, pending, stall_left,
            stop_time, energy, tard, busy_min, pre, rep, hist, device,
        )

    return step_one


@functools.lru_cache(maxsize=None)
def _chunk_fn(kind: str, dt: float, n_steps: int, penalty: float,
              day_start: float, day_end: float,
              devices: int = 1, dispatcher: str = "least-loaded"):
    """Build (and cache) the jitted scan over ``n_steps`` for one policy kind.

    A fleet's chunk (``devices > 1``) takes one more argument, ``by_arrival``
    (:meth:`BatchedJobs.by_arrival` of the caller's batch).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    step_one = make_step_fn(kind, dt, penalty, day_start, day_end, devices, dispatcher)

    @jax.jit
    def run_chunk(state, arrival, deadline, rates, valid,
                  primary, secondary, t0,
                  slice_slots, slice_rank, num_slices, o2n, watts, by_arrival=None):
        step_b = jax.vmap(
            step_one,
            in_axes=(0, None, 0, 0, 0, 0, 0, 0,
                     None, None, None, None, None, 0),
        )

        def body(carry, i):
            t = t0 + i.astype(jnp.float32) * jnp.float32(dt)
            return (
                step_b(carry, t, arrival, deadline, rates, valid,
                       primary, secondary,
                       slice_slots, slice_rank, num_slices, o2n, watts, by_arrival),
                None,
            )

        state, _ = lax.scan(body, state, jnp.arange(n_steps, dtype=jnp.int32))
        return state

    return run_chunk


def run_steps(
    state: RolloutState,
    jobs: BatchedJobs,
    policy: BatchedPolicy,
    consts: Dict[str, Any],
    *,
    t0_min: float,
    n_steps: int,
    dt_min: float = DEFAULT_DT_MIN,
    penalty_min: Optional[float] = None,
    devices: int = 1,
    dispatcher: str = "least-loaded",
    by_arrival: Optional[np.ndarray] = None,
) -> RolloutState:
    """Advance every rollout ``n_steps`` grid steps from ``t0_min``.

    The building block both :func:`simulate_batch` and the RL env share;
    the compiled program is cached per (policy kind, dt, n_steps) so
    repeated calls with the same shapes are compile-free.  ``jobs`` (and
    ``state``) are in the EDF layout of :meth:`BatchedJobs.in_edf_order`:
    the step reads EDF priority off the job index.  A fleet
    (``devices > 1``, ``state`` from ``init_state(..., devices)``) also
    takes ``by_arrival``, the layout index of each job in arrival order.
    """
    import jax
    import jax.numpy as jnp

    if penalty_min is None:
        from repro.core.simulator import REPARTITION_PENALTY_MIN

        penalty_min = REPARTITION_PENALTY_MIN
    if jobs.padded_jobs % _BLOCK != 0:
        raise ValueError(
            f"padded job axis {jobs.padded_jobs} must be a multiple of "
            f"{_BLOCK} (use BatchedJobs.from_job_lists, which pads to it)"
        )
    if not (jobs.deadline[:, 1:] >= jobs.deadline[:, :-1]).all():
        raise ValueError(
            "jobs are not in the EDF layout (deadlines must be sorted along "
            "the job axis); pass BatchedJobs.in_edf_order()"
        )
    key = (policy.kind, float(dt_min), int(n_steps), float(penalty_min),
           float(policy.day_start), float(policy.day_end))
    if devices > 1:
        if by_arrival is None:
            raise ValueError("a fleet's chunk needs by_arrival (BatchedJobs.by_arrival)")
        key += (int(devices), dispatcher)
    host = (jobs.arrival, jobs.deadline, jobs.rate_by_slots, jobs.valid,
            policy.primary, policy.secondary)
    nbytes = sum(a.nbytes for a in host if isinstance(a, np.ndarray))
    if devices > 1:
        nbytes += by_arrival.nbytes
    with obs.span("chunk.upload", bytes=nbytes):
        args = (
            state, *(jnp.asarray(a) for a in host), jnp.float32(t0_min),
            consts["slice_slots"], consts["slice_rank"], consts["num_slices"],
            consts["old_to_new"], consts["watts"],
        )
        if devices > 1:
            args += (jnp.asarray(by_arrival),)
    if obs.profiling():
        shapes = (jobs.rate_by_slots.shape, np.shape(state.slice_job),
                  np.shape(consts["old_to_new"]))  # B, J, K; S; configurations
        if (key, shapes) not in _PROFILED_CHUNKS:
            specs = jax.tree.map(lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), args)
            _PROFILED_CHUNKS[key, shapes] = [key, specs, None]
    with obs.span("chunk.dispatch"):
        return _chunk_fn(*key)(*args)


#: the chunk programs :func:`run_steps` dispatched while a profiler session
#: ran: (``_chunk_fn`` key, shapes) -> [that key, argument ``ShapeDtypeStruct``s,
#: op scopes once :func:`chunk_op_scopes` has made them]
_PROFILED_CHUNKS: Dict[Tuple, list] = {}


def chunk_op_scopes() -> Dict[str, str]:
    """``{HLO instruction name: step phase}`` of the chunk programs that
    :func:`run_steps` dispatched while a profiler session ran.

    Each such program is built and compiled again from its argument shapes,
    with JAX's persistent compilation cache off for the call: op metadata is
    not part of that cache's key, so the executable that ran may have come
    from a build without the scopes.  The instruction names are the same,
    since scopes change metadata only.  An op outside every phase maps to
    ``""`` (see :func:`op_phases`).
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    todo = [entry for entry in _PROFILED_CHUNKS.values() if entry[2] is None]
    if todo:
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            for entry in todo:
                fresh = _chunk_fn.__wrapped__(*entry[0])  # not the program in memory
                entry[2] = op_phases(fresh.lower(*entry[1]).compile().as_text())
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()
    out: Dict[str, str] = {}
    for entry in _PROFILED_CHUNKS.values():
        out.update(entry[2])
    return out


_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?(\S+) \(.*\{$")
_HLO_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?(\S+) = ")
_HLO_FUSION_CALLS = re.compile(r" fusion\(.*\bcalls=%?([^\s,]+)")
_HLO_TO_APPLY = re.compile(r"\bto_apply=%?([^\s,]+)")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PHASE_SET = frozenset(STEP_PHASES)


def _phase_of(line: str) -> str:
    m = _HLO_OP_NAME.search(line)
    names = re.split(r"[/()]", m.group(1)) if m else ()
    return next((n for n in reversed(names) if n in _PHASE_SET), "")


def op_phases(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: phase}`` for the top-level instructions of
    compiled HLO text: those of every computation that is not fused into
    an op or applied by one (a reducer, a scatter's combiner).

    An instruction's phase is the innermost of :data:`STEP_PHASES` in its
    ``op_name`` metadata; a fusion with none takes its fused root's, and
    a fused root with none the latest phase its fused instructions name.
    """
    comps: Dict[str, list] = {}  # computation -> [(name, is_root, phase, fused callee)]
    inner = set(_HLO_TO_APPLY.findall(hlo_text))
    current: list = []
    for line in hlo_text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            current = comps.setdefault(m.group(1), [])
            continue
        m = _HLO_INSTRUCTION.match(line)
        if m:
            calls = _HLO_FUSION_CALLS.search(line)
            current.append((m.group(2), bool(m.group(1)), _phase_of(line),
                            calls.group(1) if calls else None))
    inner |= {c for insts in comps.values() for *_, c in insts if c}

    def fused_phase(insts):
        # a root without metadata (a scatter or a tuple the compiler built)
        # takes the latest phase its computation's instructions name: the
        # fusion runs where that phase needs its result
        root = next((p for _, r, p, _ in insts if r), "")
        named = {p for _, _, p, _ in insts}
        return root or next((p for p in reversed(STEP_PHASES) if p in named), "")

    root_phase = {name: fused_phase(insts) for name, insts in comps.items()}
    return {
        inst: phase or (root_phase.get(callee, "") if callee else "")
        for comp, insts in comps.items() if comp not in inner
        for inst, _, phase, callee in insts
    }


def result_of(
    state: RolloutState, jobs: BatchedJobs, tables: DeviceTables
) -> BatchedResult:
    """Materialize a finished carry into a host-side :class:`BatchedResult`.

    ``state`` runs on ``jobs.in_edf_order()``; ``jobs`` is the caller's
    batch, and per-job completions (and a fleet's GPU of each job) come
    back in its job order.
    """
    stop = np.asarray(state.stop_time, dtype=np.float64)
    completion = np.empty(jobs.arrival.shape, dtype=np.float64)
    np.put_along_axis(completion, jobs.edf_order,
                      np.asarray(state.completion, dtype=np.float64), axis=1)
    device, devices = None, 1
    if state.device is not None:
        devices = int(np.shape(state.cfg)[1])
        device = np.empty(jobs.arrival.shape, dtype=np.int32)
        np.put_along_axis(device, jobs.edf_order, np.asarray(state.device), axis=1)
    return BatchedResult(
        energy_wh=np.asarray(state.energy_wh, dtype=np.float64),
        tardiness_integral=np.asarray(state.tardiness_integral, np.float64),
        busy_slot_minutes=np.asarray(state.busy_slot_minutes, np.float64),
        preemptions=np.asarray(state.preemptions, dtype=np.int64),
        repartitions=np.asarray(state.repartitions, dtype=np.int64),
        completion=completion,
        deadline=np.asarray(jobs.deadline, dtype=np.float64),
        valid=np.asarray(jobs.valid),
        num_jobs=np.asarray(jobs.num_jobs, dtype=np.int64),
        makespan_min=stop,
        util_histogram=np.asarray(state.util_hist, dtype=np.float64),
        device=device,
        devices=devices,
    )


def _horizon_bound(jobs: BatchedJobs) -> float:
    """A conservative makespan bound: serial 1g execution + two day cycles.

    Every job depletes at rate >= 1 on a 1-slot slice and EDF-FS always runs
    the queue head, so total work past the last arrival bounds the busy tail;
    the slack covers the DayNight post-drain boundary wait.
    """
    arr = np.where(jobs.valid, jobs.arrival, 0.0)
    work = np.where(jobs.valid, jobs.work, 0.0)
    per = arr.max(axis=1, initial=0.0) + work.sum(axis=1)
    return float(per.max(initial=0.0) + 2 * _DAY + 10.0)


def simulate_batch(
    jobs: BatchedJobs,
    policy: BatchedPolicy,
    *,
    tables: Optional[DeviceTables] = None,
    repartition_mode: str = "partial",
    dt_min: float = DEFAULT_DT_MIN,
    chunk_steps: int = DEFAULT_CHUNK_STEPS,
    max_minutes: Optional[float] = None,
    devices: int = 1,
    dispatcher: str = "least-loaded",
) -> BatchedResult:
    """Run every rollout to completion; the batched analogue of ``sim.run``.

    ``dt_min`` must divide 60 (so DayNight boundaries are grid points);
    ``max_minutes`` overrides the livelock guard (default: a conservative
    serial-execution bound).  ``devices > 1`` runs each rollout on a fleet
    of that many identical GPUs (``tables``), each arrival routed by
    ``dispatcher`` (:data:`DISPATCHERS`), the analogue of
    :class:`repro.fleet.FleetSimulator`; one GPU is the single-GPU run.
    Returns per-rollout aggregates; see docs/BATCHED_SIM.md §4 for how far
    they may drift from the oracle.
    """
    if tables is None:
        tables = build_tables()
    if abs(round(60.0 / dt_min) * dt_min - 60.0) > 1e-9:
        raise ValueError(f"dt_min={dt_min} must divide 60 minutes")
    if policy.batch != jobs.batch:
        raise ValueError(
            f"policy compiled for batch {policy.batch}, jobs batch {jobs.batch}"
        )
    if jobs.rate_by_slots.shape[2] != tables.max_slots + 1:
        raise ValueError("jobs rate table was built for a different device")
    if dispatcher not in DISPATCHERS:
        raise ValueError(f"unknown dispatcher {dispatcher!r}; the scan runs {DISPATCHERS}")
    consts = device_constants(tables, repartition_mode)
    by_arrival = jobs.by_arrival() if devices > 1 else None
    layout = jobs.in_edf_order()
    state = init_state(layout, policy.initial, devices)
    bound = _horizon_bound(jobs) if max_minutes is None else float(max_minutes)

    steps_done = 0
    with obs.span("batched.simulate", devices=int(devices)) as counts:
        while True:
            state = run_steps(
                state, layout, policy, consts,
                t0_min=steps_done * dt_min, n_steps=chunk_steps, dt_min=dt_min,
                penalty_min=tables.penalty_min, devices=devices, dispatcher=dispatcher,
                by_arrival=by_arrival,
            )
            steps_done += chunk_steps
            t_now = steps_done * dt_min
            with obs.span("chunk.sync"):
                stop = np.asarray(state.stop_time)
            if np.all(stop < t_now):
                break
            if t_now > bound:
                raise RuntimeError(
                    f"batched rollout still live at t={t_now:.0f} min "
                    f"(bound {bound:.0f}); unfinished rollouts: "
                    f"{int(np.sum(~(stop < t_now)))}"
                )
        counts["chunks"] = steps_done // chunk_steps
    with obs.span("batched.result") as counts:
        res = result_of(state, jobs, tables)
        if obs.profiling():
            routed = res.dispatch_counts()  # jobs per GPU, over the batch
            counts["jobs_routed_max"] = int(routed.max())
            counts["jobs_routed_min"] = int(routed.min())
        return res
