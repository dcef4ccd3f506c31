"""What the program records of itself, for the per-layer metrics that read it.

The program (``repro.obs``) keeps its host spans and counters in memory
while a profiler session runs, which is the window of a ``--trace 1`` run,
and names the step phase of each op of its chunk program
(``repro.core.batched.backend.chunk_op_scopes``).  The readers run in the
same process after the window.  Every function here gives ``None`` where
there is nothing to read: a program without these instruments, an empty
record, or a trace with no ops on chip 0.
"""

from __future__ import annotations

import importlib
from typing import List, Optional

import numpy as np


def records(run) -> Optional[list]:
    """The program's span record, or ``None`` (see the module's docstring)."""
    ops = run.trace.ops.get(0)
    if ops is None or not len(ops.start):
        return None
    try:
        obs = importlib.import_module("repro.obs")
    except ImportError:
        return None
    spans = [s for s in obs.records() if s is not None]
    return spans or None


def spans_named(run, name: str) -> Optional[List]:
    """The recorded spans called ``name``, or ``None`` with no record."""
    spans = records(run)
    return None if spans is None else [s for s in spans if s.name == name]


def mean_ms(run, name: str, per: Optional[str] = None) -> Optional[float]:
    """Total duration of the spans ``name`` in ms, over their number, or over
    the number of spans ``per``."""
    spans = spans_named(run, name)
    if not spans:
        return None
    count = len(spans_named(run, per)) if per else len(spans)
    return sum(s.end_ns - s.start_ns for s in spans) / 1e6 / count if count else None


def phase_device_us(run, phase: str) -> Optional[float]:
    """Device time of the chunk program's leaf ops in step phase ``phase``
    on chip 0, per grid step the window's chunk executions advanced, in us."""
    if records(run) is None:
        return None
    try:
        backend = importlib.import_module("repro.core.batched.backend")
        scopes = backend.chunk_op_scopes()
    except (ImportError, AttributeError):
        return None
    lo, hi = run.trace.window
    execs = sorted((e for e in run.trace.modules.get(0, ())
                    if run.info["chunk_program"] in e.name and lo <= e.start < hi),
                   key=lambda e: e.start)
    if not scopes or not execs:
        return None
    ops = run.trace.ops[0]
    # an op that encloses the next one (the scan's loop) is not a leaf
    leaf = np.concatenate([ops.start[1:] >= ops.end[:-1], [True]])
    starts = np.array([e.start for e in execs])
    ends = np.array([e.end for e in execs])
    k = np.searchsorted(starts, ops.start, side="right") - 1
    inside = (k >= 0) & (ops.start < ends[np.maximum(k, 0)])
    named = np.array([scopes.get(n.lstrip("%"), "") == phase for n in ops.names], dtype=bool)
    pick = leaf & inside & named[ops.name]
    total_ns = float(np.sum((ops.end - ops.start)[pick]))
    return total_ns / 1e3 / (len(execs) * run.info["chunk_steps"])
