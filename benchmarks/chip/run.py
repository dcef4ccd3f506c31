"""Run one cell of the chip benchmark and print its result line.

::

    python3 benchmarks/chip/run.py --workload sweep-overload --seed 7 --seconds 30 --trace 0

Everything about a cell is data found by name from ``BENCHMARK.json``:
its configuration file, its traffic mix (``mixes/<traffic>.json``), the
limits of its check (``cells/<workload>.json``) and, with ``--trace 1``,
one reader per per-layer metric (``metrics/<name>.py``).  The
configuration's ``runner`` names the module that sets the cell up and runs
its units of work (``sweep.py``).

A run: set-up (imports, the chip, the compile cache, the runner's warm-up
of every shape the window uses) is ``setup_s``; then whole units run back
to back until ``--seconds`` have passed, and the unit in flight finishes;
the rate is all the work over the time to the end of the last unit.  After
the window the runner's check compares sampled answers with the plain
reference, and each compared number is printed beside its limit, last on
standard error and last in the result line.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result; so it does where the program (``src/repro``) is absent.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: the platform a measured run needs (a test on the CPU steers this)
PLATFORM = "tpu"


class Refused(Exception):
    """The run cannot measure: no chip, no program, or a cell not found."""


def load_cell(root: str, name: str) -> dict:
    """The cell ``name`` with its configuration, mix, limits and metrics."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json; there are {sorted(cells)}")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    bench_dir = os.path.join(root, bench["paths"][0])

    def data(*parts):
        with open(os.path.join(bench_dir, *parts)) as f:
            return json.load(f)

    with open(os.path.join(root, config["file"])) as f:
        cfg = json.load(f)
    e2e_names = {m["name"] for m in bench["end_to_end"] if name in m.get("workloads", [name])}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in e2e_names else [])]
    return {
        "cell": cell, "config": cfg, "mix": data("mixes", cell["traffic"] + ".json"),
        "limits": data("cells", name + ".json")["limits"],
        "end_to_end": [m for m in bench["end_to_end"] if m["name"] in e2e_names],
        "per_layer": per_layer, "bench_dir": bench_dir,
    }


def devices_for(chips: int):
    """The chips JAX found; refuses anything but ``chips`` or more of ``PLATFORM``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != PLATFORM or len(devices) < chips:
        raise Refused(
            f"needs {chips} {PLATFORM} device(s); JAX found {len(devices)} "
            f"{devices[0].platform} ({devices[0].device_kind})"
        )
    return devices[:chips]


def read_metric(bench_dir: str, name: str, run) -> float:
    """Load ``metrics/<name>.py`` and call its ``read(run)``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("chip_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


class TracedRun:
    """What the metric readers see of a traced window."""

    def __init__(self, trace, units: int, work: float, window_s: float, info: dict) -> None:
        self.trace, self.units, self.work, self.window_s = trace, units, work, window_s
        self.info = info


def check_lines(worst: dict, limits: dict):
    """``(name, value, limit, ok)`` for every compared number; a value that
    is not finite fails and is shown as text, so that the line stays JSON."""
    return [(k, worst[k] if math.isfinite(worst[k]) else repr(worst[k]), limits[k],
             bool(worst[k] <= limits[k])) for k in limits]


def measure(args, root: str, program_root: str) -> dict:
    """Set up, run the window, check; returns the result line's object."""
    spec = load_cell(root, args.workload)
    if not os.path.isfile(os.path.join(program_root, "src", "repro", "compile_cache.py")):
        raise Refused(f"the program (src/repro) is not in {program_root}")
    src = os.path.join(program_root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    devices = devices_for(int(spec["cell"]["chips"]))

    import jax

    from benchmarks.chip import tracing
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = tracing.CompileClock()
    runner_mod = importlib.import_module("benchmarks.chip." + spec["config"]["runner"])
    runner = runner_mod.Runner(spec["config"], spec["mix"], args.seed,
                               jax.profiler.TraceAnnotation)
    runner.setup()
    setup_s = time.perf_counter() - T0

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if args.trace else None
    try:
        if trace_dir:
            tracing.start(trace_dir)
        compile_before = clock.events, clock.seconds
        work, units = 0, 0
        w0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("window"):
            while True:
                work += runner.unit(units)
                units += 1
                if time.perf_counter() - w0 >= args.seconds:
                    break
        window_s = time.perf_counter() - w0
        if trace_dir:
            tracing.stop()
        compiles = clock.events - compile_before[0]
        compile_s = clock.seconds - compile_before[1]
        stats = [d.memory_stats() or {} for d in devices]
        peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

        result = {"correct": False, "attempted": work, "failed": 0, "metrics": {}}
        dev = devices[0]
        result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                            "count": len(devices), "memory_peak_bytes": peak}
        if trace_dir:
            trace = tracing.reduce(tracing.load_xplane(trace_dir), runner_mod.SPANS)
            busy = [trace.busy_ns(i) / 1e9 for i in range(len(devices))]
            result["device"]["busy_s"] = sum(busy) / len(busy)
            result["device"]["window_s"] = window_s
            run = TracedRun(trace, units, work, window_s, runner.info())
            for m in spec["per_layer"]:
                value = read_metric(spec["bench_dir"], m["name"], run)
                if value is not None:
                    result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
            result["breakdown"] = tracing.breakdown(trace)
        else:
            values = dict(runner.end_to_end(work, window_s), setup_s=setup_s)
            for m in spec["end_to_end"]:
                result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    if compiles:
        print(f"chipbench: {compiles} compile events ({compile_s:.3f} s) inside the window",
              file=sys.stderr)
    worst, failed = runner.check(spec["limits"])
    lines = check_lines(worst, spec["limits"])
    result["correct"] = all(ok for *_, ok in lines)
    result["failed"] = failed
    result["checks"] = {k: {"value": v, "limit": lim} for k, v, lim, _ in lines}
    return result


def main(argv=None, root: str = ROOT, program_root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = measure(args, root, program_root)
    except Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    except Exception:  # the program failed: the run is not correct
        traceback.print_exc()
        result = {"correct": False, "attempted": 0, "failed": 1, "metrics": {},
                  "device": {}, "checks": {}}
        with contextlib.suppress(Exception):
            import jax

            dev = jax.devices()[0]
            result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                                "count": jax.device_count()}
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
