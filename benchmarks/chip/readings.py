"""Readings a cell's limits are set from: its check on many seeds, and the control's.

::

    python3 benchmarks/chip/readings.py --workload sweep-overload --seeds 1,2,3 --units 2

For each seed, in one process on the chip: the cell's runner runs ``--units``
units of work as a run with that seed would, then prints one JSON line with
the check's numbers for the program (``sound``) and for the control: the
plain reference with EDF replaced by arrival order, put in the program's
place (``control``).  A limit lies above every sound reading and below every
control reading of the cell (PERF.md gives both for each limit).
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

from benchmarks.chip import run, sweep  # noqa: E402


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
        print(f"readings: {e}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    limits = spec["limits"]
    for seed in (int(s) for s in args.seeds.split(",")):
        drv = sweep.Runner(spec["config"], spec["mix"], seed,
                           lambda name: contextlib.nullcontext())
        drv.setup()
        for k in range(args.units):
            drv.unit(k)
        ref = drv.reference_rows("edf")
        control = drv.reference_rows("fifo")
        for row in control:
            row["inputs"] = sweep.inputs_of(row["day"])
        sound, sound_failed = sweep.gaps(drv.program_rows(), ref, limits)
        ctrl, ctrl_failed = sweep.gaps(control, ref, limits)
        print(json.dumps({"seed": seed, "rows": len(ref), "sound": sound,
                          "sound_failed": sound_failed, "control": ctrl,
                          "control_failed": ctrl_failed}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
