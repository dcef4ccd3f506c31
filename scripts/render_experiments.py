"""Render, refresh, and check the repo's experiments book (EXPERIMENTS.md).

Usage::

    PYTHONPATH=src python scripts/render_experiments.py            # stdout
    PYTHONPATH=src python scripts/render_experiments.py --write    # refresh EXPERIMENTS.md
    PYTHONPATH=src python scripts/render_experiments.py --check    # CI gate

Sections and their deterministic inputs:

* **§Calibration** — the queue-depth analysis behind ``M_JOBS = 8``
  (``repro.core.rl.env``): re-simulated on the spot from pinned seeds.
* **§Dry-run / §Roofline** — rendered from ``artifacts/dryrun`` records
  when present, ``pending`` rows otherwise (artifacts are not checked in,
  so a fresh checkout renders the same ``pending`` state CI sees).
* **§Perf** — pointers to the benchmark entry points and the nightly
  trajectory.
* **§Batched-backend** — agreement table and speedup curve from the
  checked-in ``benchmarks/baselines/batched_agreement.json``
  (``pending`` when absent).
* **§Sweeps** — the grid registry (``repro.sweep.grids``) mapped to paper
  tables/figures and checked-in baselines.
* **§Predictive-controller** — aggregated from the checked-in
  ``benchmarks/baselines/repartition_policies.jsonl``.
* **§RL-baseline** — the batch-trained DQN raced against the forecast
  controller, from the checked-in ``benchmarks/baselines/rl_batched.json``
  (produced by ``scripts/train_rl_baseline.py``).

``--check`` fails (exit 1) when the checked-in EXPERIMENTS.md differs from
a fresh render, or when any ``*.md`` referenced from ``src/`` does not
exist — the docs gate wired into CI.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import re
import sys
from typing import Dict, List, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
EXPERIMENTS_PATH = os.path.join(REPO_ROOT, "EXPERIMENTS.md")
POLICY_BASELINE = os.path.join(
    REPO_ROOT, "benchmarks", "baselines", "repartition_policies.jsonl"
)

HEADER = """\
# EXPERIMENTS

The experiments book: calibration analyses, dry-run/roofline tables, the
sweep-grid map, and predictive-controller results.  **Generated** by
`scripts/render_experiments.py` — edit the generator, then refresh with

```bash
PYTHONPATH=src python scripts/render_experiments.py --write
```

CI runs `--check` and fails when this file is stale or a `*.md` reference
in `src/` points at a missing document.
"""


def fmt_s(x):
    if x is None:
        return "-"
    if x >= 1.0:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x*1e3:.1f}ms"
    return f"{x*1e6:.0f}us"


def fmt_b(x):
    if x is None:
        return "-"
    return f"{x/2**30:.2f}"


# ----------------------------------------------------------------------
# §Calibration — the m=8 queue-depth analysis


def calibration_md() -> str:
    """Queue-depth distribution under the paper's settings (pinned seeds).

    The paper picks the DQN state depth m = 3 from Alibaba-trace load
    analysis (§IV-D-1); our §V-A calibration produces deeper peak queues,
    and this table is the analysis that selects ``M_JOBS`` instead.
    """
    from repro.core.engine import SimulationEngine
    from repro.core.rl.env import M_JOBS
    from repro.core.schedulers import make_scheduler
    from repro.core.simulator import MIGSimulator, StaticPolicy
    from repro.core.workload import WorkloadSpec, generate_jobs

    seeds = (0, 1, 2)
    configs = (3, 6, 12)

    def stats(xs: List[int]) -> Dict[str, float]:
        xs = sorted(xs)
        n = len(xs)

        def pct(p: float) -> int:
            return xs[min(int(p * n), n - 1)] if n else 0

        return {
            "mean": sum(xs) / max(n, 1),
            "p50": pct(0.50),
            "p90": pct(0.90),
            "p99": pct(0.99),
            "max": xs[-1] if xs else 0,
        }

    out = io.StringIO()
    out.write("## Calibration\n\n")
    out.write(
        "Waiting-queue depth at decision events (EDF-SS, paper-diurnal "
        f"seeds {list(seeds)}, static configurations across the coarseness "
        "spectrum) — the load analysis that sets the DQN state depth "
        f"`M_JOBS = {M_JOBS}` in `repro.core.rl.env` (the paper derived "
        "m = 3 from Alibaba-trace load analysis, §IV-D-1):\n\n"
    )
    out.write("| config | mean | p50 | p90 | p99 | max |\n")
    out.write("|---|---|---|---|---|---|\n")
    deepest = 0
    for cfg in configs:
        depths: List[int] = []

        def hook(t, sim):
            depths.append(len(sim.queue_snapshot()))

        for seed in seeds:
            sim = MIGSimulator(make_scheduler("EDF-SS"))
            SimulationEngine(
                sim, policy=StaticPolicy(cfg),
                jobs=generate_jobs(WorkloadSpec(), seed), decision_hook=hook,
            ).drain()
        s = stats(depths)
        deepest = max(deepest, int(s["max"]))
        out.write(
            f"| {cfg} | {s['mean']:.2f} | {s['p50']} | {s['p90']} | "
            f"{s['p99']} | {s['max']} |\n"
        )
    out.write(
        f"\nm = {M_JOBS} keeps the deepest queue observed anywhere in the "
        f"configuration spectrum (max {deepest}) fully visible with "
        "headroom for heavier scenarios, while the paper's m = 3 would "
        "truncate even the p99 tail of every configuration under our §V-A "
        "calibration.  The 2+2m layout itself is unchanged from the paper.\n"
    )
    return out.getvalue()


# ----------------------------------------------------------------------
# §Dry-run / §Roofline — from artifacts/dryrun records


def dryrun_md() -> str:
    from repro.analysis.roofline import load_record
    from repro.launch.shapes import all_cells

    out = io.StringIO()
    out.write("## Dry-run — compile status and per-device memory\n\n")
    out.write(
        "Rendered from `artifacts/dryrun/` records (`python -m "
        "repro.launch.dryrun`); rows are `…` until the artifacts exist.\n\n"
    )
    out.write("| arch | shape | pod 16x16 | multi-pod 2x16x16 | args GiB/dev | temp GiB/dev | compile s |\n")
    out.write("|---|---|---|---|---|---|---|\n")
    n_ok = n_skip = n_fail = 0
    for arch, shape in all_cells():
        pod = load_record(arch, shape.name, False)
        mp = load_record(arch, shape.name, True)

        def status(r):
            if r is None:
                return "…"
            if r.get("skipped"):
                return "skip"
            return "OK" if r.get("ok") else "FAIL"

        s_pod, s_mp = status(pod), status(mp)
        if s_pod == "OK":
            n_ok += 1
        elif s_pod == "skip":
            n_skip += 1
        elif s_pod == "FAIL":
            n_fail += 1
        args = temp = comp = None
        if pod and pod.get("ok") and not pod.get("skipped"):
            args = pod.get("argument_size_in_bytes")
            temp = pod.get("temp_size_in_bytes")
            comp = pod.get("compile_seconds")
        out.write(
            f"| {arch} | {shape.name} | {s_pod} | {s_mp} | {fmt_b(args)} | "
            f"{fmt_b(temp)} | {f'{comp:.0f}' if comp else '-'} |\n"
        )
    out.write(f"\npod cells: {n_ok} OK, {n_skip} skipped (DESIGN.md §4), {n_fail} failed.\n")
    return out.getvalue()


def roofline_md() -> str:
    from repro.analysis.roofline import roofline_row
    from repro.launch.shapes import all_cells

    out = io.StringIO()
    out.write("## Roofline — per (arch x shape), single pod (256 chips)\n\n")
    out.write("| arch | shape | t_comp | t_mem | t_coll | dominant | MODEL/HLO | roofline frac | note |\n")
    out.write("|---|---|---|---|---|---|---|---|---|\n")
    for arch, shape in all_cells():
        row = roofline_row(arch, shape.name)
        if row is None:
            out.write(f"| {arch} | {shape.name} | … | | | | | | pending |\n")
            continue
        if row.get("skipped"):
            out.write(f"| {arch} | {shape.name} | skip | | | | | | {row.get('reason','')} |\n")
            continue
        if row.get("failed"):
            out.write(f"| {arch} | {shape.name} | FAIL | | | | | | |\n")
            continue
        note = _note(row)
        out.write(
            f"| {arch} | {shape.name} | {fmt_s(row['t_compute_s'])} | "
            f"{fmt_s(row['t_memory_s'])} | {fmt_s(row['t_collective_s'])} | "
            f"{row['dominant']} | {row['useful_ratio']:.2f} | "
            f"{row['roofline_fraction']:.2%} | {note} |\n"
        )
    return out.getvalue()


def _note(row) -> str:
    d = row["dominant"]
    if d == "compute":
        if (row["useful_ratio"] or 1) < 0.6:
            return "cut non-useful FLOPs (remat/attention waste)"
        return "near compute roof; fuse/overlap collectives"
    if d == "memory":
        return "raise arithmetic intensity (bigger tiles, bf16 temps, fuse)"
    return "reshard to shrink collective payload / overlap with compute"


# ----------------------------------------------------------------------
# §Perf


def perf_md() -> str:
    return (
        "## Perf\n\n"
        "End-to-end performance entry points (numbers live in\n"
        "artifacts and the nightly trajectory, not in this file):\n\n"
        "* `python -m benchmarks.run --scale 4 --workers 8` — the paper-table\n"
        "  battery through the sweep engine (the reference EXPERIMENTS\n"
        "  battery used `--scale 4`).\n"
        "* `python scripts/bench_engine.py` — SimulationEngine events/sec\n"
        "  micro-benchmark (paper-diurnal, `--load-scale 0.1`); CI gates a\n"
        "  conservative floor, nightly folds the record into the trajectory.\n"
        "* `python scripts/bench_batched.py` — batched-backend speedup\n"
        "  curve vs the oracle (events/sec-equivalent; §Batched-backend\n"
        "  below renders the checked-in record); `bench_engine.py\n"
        "  --backend batched` delegates here.\n"
        "* `BENCH_nightly.json` — per-grid wall-clock / cache-hit / engine\n"
        "  events/sec trajectory appended by `scripts/bench_nightly.py` from\n"
        "  the nightly workflow.\n"
        "* DQN reference trainings use 900+ episodes\n"
        "  (`examples/dynamic_repartitioning_day.py`); short trainings\n"
        "  underperform the heuristic baseline.\n"
    )


# ----------------------------------------------------------------------
# §Batched-backend — agreement + speedup from the checked-in record

BATCHED_AGREEMENT = os.path.join(
    REPO_ROOT, "benchmarks", "baselines", "batched_agreement.json"
)


def batched_md() -> str:
    out = io.StringIO()
    out.write("## Batched-backend — oracle agreement and speedup curve\n\n")
    out.write(
        "`repro.core.batched` re-runs the same physics as fixed-timestep\n"
        "`vmap`/`lax.scan` rollouts (docs/BATCHED_SIM.md, DESIGN.md §8).\n"
        "The event engine stays the bit-exact oracle; the batched backend\n"
        "agrees within the docs/BATCHED_SIM.md §4 tolerances and its advantage\n"
        "grows with load, because the oracle's per-event cost is O(queue)\n"
        "while the scan's per-step cost is flat.\n\n"
    )
    if not os.path.exists(BATCHED_AGREEMENT):
        out.write(
            "*(record `batched_agreement.json` not yet generated — run\n"
            "`PYTHONPATH=src python scripts/bench_batched.py "
            "--write-agreement`)*\n"
        )
        return out.getvalue()

    with open(BATCHED_AGREEMENT, encoding="utf-8") as f:
        rec = json.load(f)

    out.write(
        f"Measured on `{rec['scenario']}` × `{rec['policy']}` at "
        f"`dt = {rec['dt_min']}` min (single-core CPU reference box, "
        "`scripts/bench_batched.py --write-agreement`):\n\n"
    )
    out.write(
        "| load | batch | oracle ev/s | batched ev_eq/s | ratio "
        "| energy rel | tardiness rel | repartitions |\n"
    )
    out.write("|---|---|---|---|---|---|---|---|\n")
    for p in rec["points"]:
        a = p["agreement"]
        ratio = f"**{p['ratio_vs_oracle']:.1f}x**" if (
            p["load_scale"] == rec["headline_load_scale"]
        ) else f"{p['ratio_vs_oracle']:.1f}x"
        out.write(
            f"| {p['load_scale']:g} | {p['batch']} "
            f"| {p['oracle_events_per_sec']:,.0f} "
            f"| {p['events_equiv_per_sec']:,.0f} | {ratio} "
            f"| {a['energy_rel_max']:.2%} | {a['tardiness_rel_max']:.2%} "
            f"| {'exact' if a['repartitions_exact'] else 'MISMATCH'} |\n"
        )
    light = min(rec["points"], key=lambda p: p["load_scale"])
    out.write(
        "\n(`tardiness rel` divides by `max(oracle, 0.25 min)`; the "
        f"{light['agreement']['tardiness_rel_max']:.0%} at load "
        f"{light['load_scale']:g} is a floor artifact — the absolute error "
        f"there is {light['agreement']['tardiness_abs_max']:.2f} min.)\n"
    )
    out.write(
        f"\nHeadline: **{rec['ratio_vs_oracle']:.1f}x** the oracle's\n"
        f"events/sec at `load_scale = {rec['headline_load_scale']:g}`\n"
        "(the paper's overload regime), with repartition counts exact at\n"
        "every point — the speedup is not bought with accuracy.  The ratio\n"
        "crosses 1x near `load_scale ≈ 1.2`; below that the oracle wins\n"
        "and should be used.  The gate CI tracks is the *ratio* (both\n"
        "backends on the same box), never absolute events/sec\n"
        "(`scripts/bench_nightly.py --gate-batched-ratio`).  Regenerate\n"
        "with the CONTRIBUTING.md \"Batched-backend tolerances\" recipe.\n"
    )
    return out.getvalue()


# ----------------------------------------------------------------------
# §Sweeps — grid registry -> paper anchors -> baselines

GRID_ANCHORS = {
    "table2_schedulers": "Table II",
    "fig4_preemption": "Fig. 4",
    "fig6_utilization": "Fig. 6",
    "fig7_fig8_arrival": "Figs. 7-8",
    "fig9_fig10_split": "Figs. 9-10",
    "table3_repartitioning": "Table III",
    "fig11_preferences": "Fig. 11",
    "fleet_scaling": "beyond-paper (fleet)",
    "dispatchers": "beyond-paper (online vs fluid dispatch)",
    "scenario_matrix": "beyond-paper (scenarios)",
    "repartition_policies": "beyond-paper (§V-C conjecture)",
    "repartition_modes": "beyond-paper (partial vs full-drain reconfiguration)",
    "serving_matrix": "beyond-paper (multi-tenant SLO serving, DESIGN.md §9)",
    "smoke": "CI smoke (Table II subset)",
}


def sweeps_md() -> str:
    from repro.sweep.grids import GRIDS

    out = io.StringIO()
    out.write("## Sweeps — grid → paper table/figure map\n\n")
    out.write(
        "Run any grid with `python -m repro.sweep <grid> --workers 4`; CI\n"
        "gates the baselined grids at `--scale 0.1` (see CONTRIBUTING.md for\n"
        "the regeneration recipe after a `SIM_VERSION` bump).\n\n"
    )
    out.write("| grid | reproduces | baseline | description |\n")
    out.write("|---|---|---|---|\n")
    for name in sorted(GRIDS):
        grid = GRIDS[name]
        baseline = ""
        for candidate in (f"{name}.jsonl", "smoke_sweep.jsonl" if name == "smoke" else ""):
            if candidate and os.path.exists(
                os.path.join(REPO_ROOT, "benchmarks", "baselines", candidate)
            ):
                baseline = f"`benchmarks/baselines/{candidate}`"
                break
        out.write(
            f"| `{name}` | {GRID_ANCHORS.get(name, '')} | {baseline} | {grid.doc} |\n"
        )
    return out.getvalue()


# ----------------------------------------------------------------------
# §Dispatchers — online (real-state) vs fluid (estimate) routing

DISPATCHERS_BASELINE = os.path.join(
    REPO_ROOT, "benchmarks", "baselines", "dispatchers.jsonl"
)


def _baseline_rows(path: str, grid_name: str):
    """Aggregate a checked-in baseline JSONL through its grid definition."""
    from repro.sweep.grids import GRIDS

    cells, results = [], []
    with open(path) as f:
        for line in f:
            if line.strip():
                rec = json.loads(line)
                cells.append(rec["cell"])
                results.append(rec["result"])
    return GRIDS[grid_name].aggregate(cells, results)


def dispatchers_md() -> str:
    out = io.StringIO()
    out.write("## Dispatchers — what real dispatch-time state is worth\n\n")
    out.write(
        "Fleet dispatch is *online* since `mig-sim-3`: per-device\n"
        "simulation engines are co-advanced to every arrival and the\n"
        "dispatcher observes real queue/partition/repartition state through\n"
        "engine snapshots (`repro.fleet`, DESIGN.md §6).  The previous\n"
        "two-phase *fluid* pre-split (a backlog estimate draining at peak\n"
        "slot rate) is kept as `dispatch_info=\"fluid\"`, and the\n"
        "`dispatchers` grid races both modes so the information gap is a\n"
        "reported number.  `state-aware` routes on signals the fluid model\n"
        "cannot produce (in-flight repartitions, free slices) and therefore\n"
        "has no fluid row.\n\n"
    )
    if not os.path.exists(DISPATCHERS_BASELINE):
        out.write("*(baseline `dispatchers.jsonl` not yet generated)*\n")
        return out.getvalue()

    rows = _baseline_rows(DISPATCHERS_BASELINE, "dispatchers")

    out.write(
        "ET per fleet × dispatcher × dispatch mode (shared per-fleet\n"
        "scaling factor `a`; lower is better) from the checked-in\n"
        "`--scale 0.1` baseline:\n\n"
    )
    out.write("| fleet | dispatcher | ET online | ET fluid | online gain |\n")
    out.write("|---|---|---|---|---|\n")
    for row in rows:
        fluid = f"{row['ET_fluid']:.4f}" if row["ET_fluid"] is not None else "—"
        gain = (
            f"{row['online_gain_pct']:+.2f}%"
            if row["online_gain_pct"] is not None
            else "—"
        )
        out.write(
            f"| {row['fleet']} | {row['dispatcher']} | {row['ET_online']:.4f} "
            f"| {fluid} | {gain} |\n"
        )
    out.write(
        "\nRound-robin ignores state, so its gap is identically zero — a\n"
        "built-in control that the two modes share physics.  Where the gap\n"
        "is non-zero the two information models genuinely route\n"
        "differently; the sign varies by fleet shape because the fluid\n"
        "estimate's peak-rate drain flatters small devices (it dispatches\n"
        "as if an A30 drained like an A100, which sometimes luckily\n"
        "load-balances).  Regenerate with `python -m repro.sweep\n"
        "dispatchers --scale 0.1` and compare via `--check-baseline`.\n"
    )
    return out.getvalue()


# ----------------------------------------------------------------------
# §Repartition-modes — partial vs full-drain reconfiguration

MODES_BASELINE = os.path.join(
    REPO_ROOT, "benchmarks", "baselines", "repartition_modes.jsonl"
)


def repartition_modes_md() -> str:
    out = io.StringIO()
    out.write("## Repartition-modes — what partial reconfiguration is worth\n\n")
    out.write(
        "Since `mig-sim-4` partitions are *slot-placed* (NVIDIA placement\n"
        "grid, DESIGN.md §7) and repartitioning is *partial* by default:\n"
        "only the slice instances that differ between the old and new\n"
        "layout are destroyed/created, jobs on surviving instances run\n"
        "through the 4 s stall, and the stall is charged against the\n"
        "affected slots only.  The legacy full-drain model — every running\n"
        "job preempted, the whole GPU blocked — is kept as\n"
        "`repartition_mode=\"drain\"` and reproduces pre-`mig-sim-4`\n"
        "numbers bit-identically.  The `repartition_modes` grid races both\n"
        "models for every repartitioning policy family × scenario on\n"
        "identical job streams.\n\n"
    )
    if not os.path.exists(MODES_BASELINE):
        out.write("*(baseline `repartition_modes.jsonl` not yet generated)*\n")
        return out.getvalue()

    rows = _baseline_rows(MODES_BASELINE, "repartition_modes")

    out.write(
        "ET and preemptions per scenario × family × transition model\n"
        "(shared per-scenario ET scale factor `a`; lower is better) from\n"
        "the checked-in `--scale 0.1` baseline:\n\n"
    )
    out.write(
        "| scenario | family | ET drain | ET partial | preempt drain "
        "| preempt partial | repart drain | repart partial |\n"
    )
    out.write("|---|---|---|---|---|---|---|---|\n")
    for row in rows:
        out.write(
            f"| {row['scenario']} | {row['family']} | {row['ET_drain']:.4f} "
            f"| {row['ET_partial']:.4f} | {row['preemptions_drain']:.1f} "
            f"| {row['preemptions_partial']:.1f} "
            f"| {row['repartitions_drain']:.1f} "
            f"| {row['repartitions_partial']:.1f} |\n"
        )
    # narrative keyed off the families actually present in the baseline —
    # the list is owned by grids.REPARTITION_MODE_FAMILIES and may change
    paper = {
        r["family"]: r for r in rows if r["scenario"] == "paper-diurnal"
    }
    fc, hr = paper.get("Forecast"), paper.get("Heuristic")
    if fc is None or hr is None:
        out.write(
            "\nRegenerate with `python -m repro.sweep repartition_modes "
            "--scale 0.1` and compare via `--check-baseline`.\n"
        )
        return out.getvalue()
    out.write(
        "\nThe reactive heuristic is the biggest beneficiary — it switches\n"
        "hundreds of times a day, and under partial transitions the jobs\n"
        "on surviving slices stop being collateral (paper-diurnal: "
        f"{hr['preemptions_drain']:.0f} → {hr['preemptions_partial']:.0f}\n"
        "preemptions).  The predictive controller prices the partial\n"
        "transition in its MPC lookahead (surviving capacity keeps serving\n"
        "through the stall, displaced work pays the requeue) and times\n"
        "switches opportunistically at displacement-free instants, cutting\n"
        f"preemptions {fc['preemptions_drain']:.1f} → "
        f"{fc['preemptions_partial']:.1f} at equal-or-better ET\n"
        f"({fc['ET_drain']:.4f} → {fc['ET_partial']:.4f}) with fewer\n"
        "repartitions — the paper's §VI conjecture (cheap, frequent\n"
        "reconfiguration) moving in the predicted direction.  DayNightMIG\n"
        "switches twice a day at fixed clock times regardless of model, so\n"
        "its rows double as a drain/partial physics control.  Regenerate\n"
        "with `python -m repro.sweep repartition_modes --scale 0.1` and\n"
        "compare via `--check-baseline`.\n"
    )
    return out.getvalue()


# ----------------------------------------------------------------------
# §Predictive-controller — from the checked-in baseline


def predictive_md() -> str:
    out = io.StringIO()
    out.write("## Predictive-controller results\n\n")
    out.write(
        "The paper closes observing that preferred configurations recur at\n"
        "specific times of day, \"suggesting a policy for predictive and\n"
        "automatic reconfiguration\" (§V-C).  `repro.forecast` implements\n"
        "that policy family: a Fourier day-model + EWMA bias forecaster\n"
        "driving a model-predictive controller that rolls a fluid/queueing\n"
        "approximation forward per candidate configuration (lateness priced\n"
        "from a pinned §V-A job sample, M/G/c stochastic-wait correction,\n"
        "duty-cycle-correct energy) and repartitions under asymmetric\n"
        "hysteresis.  Default candidate set: the Fig.-11 coarse family\n"
        "`(1, 2, 3)` — full GPU overnight (race-to-idle), 4g+3g shoulders,\n"
        "4g+2g+1g through the plateau.\n\n"
    )
    if not os.path.exists(POLICY_BASELINE):
        out.write("*(baseline `repartition_policies.jsonl` not yet generated)*\n")
        return out.getvalue()

    rows = _baseline_rows(POLICY_BASELINE, "repartition_policies")

    families = [
        k[len("ET_"):] for k in rows[0] if k.startswith("ET_")
    ]
    out.write(
        "ET per policy family × scenario (shared per-scenario scaling "
        "factor `a`; lower is better) from the checked-in `--scale 0.1` "
        "baseline:\n\n"
    )
    out.write("| scenario | " + " | ".join(families) + " | forecast beats static |\n")
    out.write("|---|" + "---|" * (len(families) + 1) + "\n")
    for row in rows:
        cells_md = " | ".join(f"{row['ET_' + f]:.4f}" for f in families)
        beats = "**yes**" if row["forecast_beats_static"] else "no"
        out.write(f"| {row['scenario']} | {cells_md} | {beats} |\n")
    paper_row = next(r for r in rows if r["scenario"] == "paper-diurnal")
    out.write(
        "\nOn the paper's own workload the predictive controller beats\n"
        "static partitioning on ET while repartitioning ~"
        f"{paper_row['repartitions_Forecast']:.0f}"
        f" times/day (vs ~{paper_row['repartitions_Heuristic']:.0f} for the\n"
        "reactive queue heuristic, which stays the envelope on most\n"
        "scenarios by exploiting instant reaction to\n"
        "every queue change).  The heavy-tail scenarios break the §V-A\n"
        "job-mix assumptions baked into the controller's lateness curves and\n"
        "stay static-equivalent — the open head-room the DQN (and a\n"
        "retrained lateness sample) can chase.  Regenerate with\n"
        "`python -m repro.sweep repartition_policies --scale 0.1` and\n"
        "compare via `--check-baseline`.\n"
    )
    return out.getvalue()


# ----------------------------------------------------------------------
# §RL-baseline — the batch-trained DQN vs the forecast controller

RL_BASELINE = os.path.join(
    REPO_ROOT, "benchmarks", "baselines", "rl_batched.json"
)


def rl_md() -> str:
    out = io.StringIO()
    out.write("## RL baseline — batch-trained DQN vs forecast\n\n")
    out.write(
        "The fused on-device trainer (`repro.core.rl.batched_train`,\n"
        "DESIGN.md §11) advances B rollouts *and* the DQN update inside one\n"
        "jitted scan — `scripts/bench_rl.py` measures ≥50× the host loop's\n"
        "env-steps/sec at the headline load of its curve, which is what\n"
        "makes the training budget below an interactive job instead of an\n"
        "overnight one.  `scripts/train_rl_baseline.py` trains with fixed\n"
        "seeds over a scenario × load-scale randomized episode stream and\n"
        "races the greedy policy (on its 15-min training cadence) against\n"
        "the predictive forecast controller, same seeds → identical job\n"
        "streams:\n\n"
    )
    if not os.path.exists(RL_BASELINE):
        out.write("*(baseline `rl_batched.json` not yet generated)*\n")
        return out.getvalue()
    with open(RL_BASELINE, encoding="utf-8") as f:
        entry = json.load(f)
    out.write("| scenario | ET DQN | ET Forecast | DQN beats forecast |\n")
    out.write("|---|---|---|---|\n")
    for row in entry["rows"]:
        beats = "**yes**" if row["dqn_beats_forecast"] else "no"
        out.write(
            f"| {row['scenario']} | {row['ET_DQN']:.4f} "
            f"| {row['ET_Forecast']:.4f} | {beats} |\n"
        )
    tr = entry["train"]
    wins = ", ".join(f"`{w}`" for w in entry["families_beaten"]) or "none"
    out.write(
        f"\nTrained {tr['episodes']} episodes (batch {tr.get('batch', 64)},"
        f" seed {tr['seed']}) over {len(tr['scenarios'])} scenario"
        f" families at load scales {tr['load_scale_range']}; the ROADMAP\n"
        f"item-4 gating rule — beat the forecast controller on ≥1 scenario\n"
        f"family — holds on: {wins}.  CI pins the params probe and this\n"
        "file's claim (tests/test_batched_train.py); nightly re-evaluates\n"
        "the checked-in params (`train_rl_baseline.py --check`) and gates\n"
        "training throughput (`bench_rl.py --min-ratio 50`,\n"
        "`bench_nightly.py --gate-rl-ratio`).  Retrain + regenerate with\n"
        "`python scripts/train_rl_baseline.py`.\n"
    )
    return out.getvalue()


# ----------------------------------------------------------------------
# §Serving — multi-tenant SLO attainment under fragmentation-aware dispatch

SERVING_BASELINE = os.path.join(
    REPO_ROOT, "benchmarks", "baselines", "serving_matrix.jsonl"
)


def serving_md() -> str:
    out = io.StringIO()
    out.write("## Serving — multi-tenant SLO attainment\n\n")
    out.write(
        "The `multi-tenant-serving` scenario replaces the paper's anonymous\n"
        "batch trace with named tenant request streams: each tenant is a\n"
        "model config mapped memory-first onto a MIG slice class\n"
        "(`repro.core.serving`, DESIGN.md §9), every request carries a\n"
        "latency SLO, and per-tenant attainment is threaded exactly through\n"
        "`SimResult` and the fleet aggregation.  The `serving_matrix` grid\n"
        "races the dispatchers over three tenant mixes on two fleets; the\n"
        "`fragmentation-aware` dispatcher adds a slice-class misfit term and\n"
        "a post-placement fragmentation penalty over the free-slot geometry\n"
        "to the state-aware start-delay proxy.\n\n"
    )
    if not os.path.exists(SERVING_BASELINE):
        out.write("*(baseline `serving_matrix.jsonl` not yet generated)*\n")
        return out.getvalue()

    rows = _baseline_rows(SERVING_BASELINE, "serving_matrix")

    out.write(
        "Fleet SLO attainment (request-weighted; higher is better) and\n"
        "energy per fleet × mix × dispatcher from the checked-in\n"
        "`--scale 0.1` baseline:\n\n"
    )
    out.write("| fleet | mix (load) | dispatcher | SLO attainment | energy (Wh) | ET |\n")
    out.write("|---|---|---|---|---|---|\n")
    for row in rows:
        out.write(
            f"| {row['fleet']} | {row['mix']} ({row['load_scale']:g}) "
            f"| {row['dispatcher']} | {row['slo_attainment']:.4f} "
            f"| {row['energy_wh']:.0f} | {row['ET']:.4f} |\n"
        )
    out.write(
        "\nOn the large-heavy mix fragmentation-aware beats least-loaded on\n"
        "SLO attainment at lower energy on *both* fleets (the CI-gated\n"
        "acceptance row, pinned in `tests/test_serving.py`): keeping a\n"
        "wide instance placeable is exactly what the mixtral-class tenants\n"
        "need.  The saturated mixed-fleet balanced row shows the limit —\n"
        "when offered load exceeds what the fleet can serve within SLO, no\n"
        "routing policy recovers it and blind round-robin's spreading\n"
        "incidentally wins.  Regenerate with `python -m repro.sweep\n"
        "serving_matrix --scale 0.1` and compare via `--check-baseline`.\n"
    )
    return out.getvalue()


# ----------------------------------------------------------------------
# document assembly + checks


def build_markdown() -> str:
    parts = [
        HEADER,
        calibration_md(),
        dryrun_md(),
        roofline_md(),
        perf_md(),
        batched_md(),
        sweeps_md(),
        dispatchers_md(),
        repartition_modes_md(),
        predictive_md(),
        rl_md(),
        serving_md(),
    ]
    return "\n".join(part.rstrip() + "\n" for part in parts)


# any path-qualified or bare markdown reference; the matched path is
# resolved verbatim against the repo root (no prefix stripping), so a
# subdirectory-qualified reference is checked at exactly that path
_MD_REF = re.compile(r"\b((?:[A-Za-z0-9_.-]+/)*[A-Za-z][\w.-]*\.md)\b")


def check_doc_refs(root: str = REPO_ROOT) -> List[Tuple[str, str]]:
    """Dangling ``*.md`` references in ``src/`` (and ``scripts/``)."""
    dangling: List[Tuple[str, str]] = []
    for base in ("src", "scripts"):
        for dirpath, dirnames, filenames in os.walk(os.path.join(root, base)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                with open(path, encoding="utf-8") as f:
                    text = f.read()
                for ref in sorted(set(_MD_REF.findall(text))):
                    if not os.path.exists(os.path.join(root, ref)):
                        dangling.append((os.path.relpath(path, root), ref))
    return dangling


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--write", action="store_true",
                    help="write EXPERIMENTS.md at the repo root")
    ap.add_argument("--check", action="store_true",
                    help="fail if EXPERIMENTS.md is stale or a doc reference dangles")
    args = ap.parse_args(argv)

    rendered = build_markdown()

    if args.check:
        failed = False
        dangling = check_doc_refs()
        for path, ref in dangling:
            print(f"DANGLING DOC REF: {path} references missing {ref}", file=sys.stderr)
            failed = True
        if not os.path.exists(EXPERIMENTS_PATH):
            print("EXPERIMENTS.md does not exist; run --write", file=sys.stderr)
            failed = True
        else:
            with open(EXPERIMENTS_PATH, encoding="utf-8") as f:
                current = f.read()
            if current != rendered:
                print(
                    "EXPERIMENTS.md is stale: regenerate with "
                    "`PYTHONPATH=src python scripts/render_experiments.py --write`",
                    file=sys.stderr,
                )
                failed = True
        if not failed:
            print("EXPERIMENTS.md up to date; all doc references resolve")
        return 1 if failed else 0

    if args.write:
        with open(EXPERIMENTS_PATH, "w", encoding="utf-8") as f:
            f.write(rendered)
        print(f"wrote {EXPERIMENTS_PATH}")
        return 0

    print(rendered, end="")
    return 0


if __name__ == "__main__":
    sys.exit(main())
