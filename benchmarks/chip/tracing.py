"""Trace capture and its reduction to the intervals the per-layer metrics read.

A traced run records the window with JAX's profiler (the Python tracer
off: it would record every call of the host generator).  The reduction
reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` into plain
lists, so that a small trace written as JSON (``testdata/``) goes through
the same code:

* per chip, the device's op events and its program (module) executions,
  from the planes named ``/device:TPU:<n>`` (lines ``XLA Ops`` and
  ``XLA Modules``);
* the benchmark's own host spans (``jax.profiler.TraceAnnotation`` around
  each call into a layer), by name, from the host plane.

All times are nanoseconds on the profiler's clock.
"""

from __future__ import annotations

import glob
import json
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"

Interval = Tuple[int, int]


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading a cached
    executable), summed from its monitoring events; a copy of chip_smoke.py's."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += duration
            self.events += 1


@dataclass(frozen=True)
class Event:
    name: str
    start: int
    end: int


@dataclass
class Ops:
    """One chip's op events: start and end times, and an index into ``names``."""

    start: np.ndarray
    end: np.ndarray
    name: np.ndarray
    names: List[str]

    @classmethod
    def of(cls, events) -> "Ops":
        ids: Dict[str, int] = {}
        n = len(events)
        start, end, name = np.empty(n, np.int64), np.empty(n, np.int64), np.empty(n, np.int64)
        for i, (nm, s, d) in enumerate(events):
            start[i], end[i] = s, s + d  # float ns from the profiler, whole here
            name[i] = ids.setdefault(nm, len(ids))
        order = np.argsort(start, kind="stable")
        return cls(start[order], end[order], name[order], list(ids))


@dataclass
class Trace:
    """What a traced window leaves for the metric readers."""

    ops: Dict[int, Ops] = field(default_factory=dict)  # chip -> its op events
    modules: Dict[int, List[Event]] = field(default_factory=dict)  # chip -> executions
    spans: List[Event] = field(default_factory=list)  # the benchmark's host spans
    window: Optional[Interval] = None

    def spans_named(self, name: str) -> List[Event]:
        return [s for s in self.spans if s.name == name]

    def busy(self, chip: int) -> Tuple[np.ndarray, np.ndarray]:
        """Union of the chip's op intervals: sorted, disjoint starts and ends."""
        ops = self.ops.get(chip)
        if ops is None or not len(ops.start):
            return np.empty(0, np.int64), np.empty(0, np.int64)
        reach = np.maximum.accumulate(ops.end)
        new = np.concatenate([[True], ops.start[1:] > reach[:-1]])
        last = np.concatenate([np.flatnonzero(new)[1:] - 1, [len(new) - 1]])
        return ops.start[new], reach[last]

    def busy_ns(self, chip: int) -> int:
        lo, hi = self.window
        a, b = self.busy(chip)
        return int(np.sum(np.clip(np.minimum(b, hi) - np.maximum(a, lo), 0, None)))


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def _short(name: str) -> str:
    """An HLO op's name without its shapes and operands (``%fusion.208``)."""
    return name.split(" = ", 1)[0]


def load_xplane(log_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``log_dir`` as plain planes/lines/events.

    Of the device planes only the op and module lines are kept, with op
    names cut to the op; of the host plane only the benchmark's spans
    (every event is kept, the reduction picks them by name).
    """
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            short: Dict[str, str] = {}
            events = []
            for e in line.events:
                name = e.name
                if device:
                    name = short.get(name) or short.setdefault(name, _short(name))
                events.append((name, e.start_ns, e.duration_ns))
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def reduce(raw: dict, span_names: Iterable[str], window_span: str = "window") -> Trace:
    """Device ops and executions per chip, and the named host spans."""
    names = set(span_names) | {window_span}
    trace = Trace()
    for plane in raw["planes"]:
        pname = plane["name"]
        if pname.startswith(DEVICE_PREFIX):
            chip = int(pname[len(DEVICE_PREFIX):])
            for line in plane["lines"]:
                if line["name"] == OPS_LINE:
                    trace.ops[chip] = Ops.of(line["events"])
                elif line["name"] == MODULES_LINE:
                    trace.modules[chip] = [Event(n, s, s + d) for n, s, d in line["events"]]
        elif pname == HOST_PLANE:
            for line in plane["lines"]:
                trace.spans += [Event(n, s, s + d) for n, s, d in line["events"] if n in names]
    trace.spans.sort(key=lambda e: e.start)
    windows = trace.spans_named(window_span)
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} {window_span!r} spans, not 1")
    trace.window = (windows[0].start, windows[0].end)
    return trace


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device ops that took most time, and the longest idle gaps named by
    the innermost benchmark span open in them (chip 0).

    An op that encloses the next one (a loop, a conditional) is left out of
    the ranking, so that its body's ops are counted once.
    """
    ops = trace.ops.get(0)
    ranked: List[Tuple[str, int]] = []
    if ops is not None and len(ops.start):
        leaf = np.concatenate([ops.start[1:] >= ops.end[:-1], [True]])
        total = np.bincount(ops.name[leaf], weights=(ops.end - ops.start)[leaf],
                            minlength=len(ops.names))
        ranked = [(ops.names[i], int(total[i])) for i in np.argsort(-total)[:top] if total[i] > 0]
    lo, hi = trace.window
    a, b = trace.busy(0)
    keep = (b > lo) & (a < hi)
    a, b = np.clip(a[keep], lo, hi), np.clip(b[keep], lo, hi)
    gap_start = np.concatenate([[lo], b])
    gap_end = np.concatenate([a, [hi]])
    length = gap_end - gap_start
    named = []
    for i in np.argsort(-length)[:top]:
        if length[i] <= 0:
            break
        mid = (gap_start[i] + gap_end[i]) // 2
        open_spans = [s for s in trace.spans if s.start <= mid < s.end and s.name != "window"]
        name = min(open_spans, key=lambda s: s.end - s.start).name if open_spans else "window"
        named.append((name, int(length[i])))
    return {
        "device_ops": [[n, ns / 1e9] for n, ns in ranked],
        "idle_gaps": [[n, ns / 1e9] for n, ns in named],
    }
