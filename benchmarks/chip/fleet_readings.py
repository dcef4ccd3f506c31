"""Readings a fleet cell's limits are set from: its check on many seeds, and the control's.

::

    python3 benchmarks/chip/fleet_readings.py --workload fleet8-paper-load --seeds 1,2,3 --units 1

As ``readings.py``, for a cell whose runner is ``fleet_sweep.py``: for each
seed, in one process on the chip, the runner runs ``--units`` units of work
as a run with that seed would, then prints one JSON line with the check's
numbers for the program (``sound``), for the control: the fleet reference
with round-robin in place of the configuration's dispatcher, put in the
program's place (``control``), and for the fleet reference computed in
bfloat16, the precision below the program's float32 (``low_precision``).
A limit lies above every sound reading and fails the other two.
``near_ties`` counts the arrivals over the sound rows that the reference
routed as the program did, on a float32 rounding of a backlog tie.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import fleet_sweep, run  # noqa: E402

#: the precision below the program's float32 (the TPU's own 16-bit type)
LOW_PRECISION = "bfloat16"


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--units", type=int, default=1)
    args = ap.parse_args(argv)
    spec = run.load_cell(root, args.workload)
    sys.path.insert(0, os.path.join(root, "src"))
    try:
        run.devices_for(int(spec["cell"]["chips"]))
    except run.Refused as e:
        print(f"fleet_readings: {e}", file=sys.stderr)
        return 2
    import ml_dtypes
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    limits = spec["limits"]
    for seed in (int(s) for s in args.seeds.split(",")):
        drv = fleet_sweep.Runner(spec["config"], spec["mix"], seed,
                                 lambda name: contextlib.nullcontext())
        drv.setup()
        for k in range(args.units):
            drv.unit(k)
        rows = drv.program_rows()
        ref = drv.reference_rows(follow=rows)
        sound, sound_failed = fleet_sweep.gaps(rows, ref, limits)
        near_ties = sum(r["near_ties"] for r in ref)
        ctrl, ctrl_failed = drv.control_check(limits)
        low, low_failed = drv.low_precision_check(limits, getattr(ml_dtypes, LOW_PRECISION))
        print(json.dumps({"seed": seed, "rows": len(drv.samples), "sound": sound,
                          "sound_failed": sound_failed, "near_ties": near_ties, "control": ctrl,
                          "control_failed": ctrl_failed, "low_precision": low,
                          "low_precision_failed": low_failed}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
