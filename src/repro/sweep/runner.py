"""The parallel sweep runner: cache lookup, process fan-out, JSONL artifacts.

Execution model
---------------
* Every cell gets a content hash; memoized results are served from the
  :class:`SweepCache` (the ``--resume`` path — an interrupted sweep re-runs
  only missing cells because each result is persisted as it arrives).
* Misses run through ``run_cell`` — inline for ``workers <= 1``, else fanned
  out over a ``ProcessPoolExecutor``.  Determinism does not depend on the
  worker count: a cell's seed travels inside the cell, and results are
  re-ordered back into grid order before aggregation/serialization.
* The artifact is a byte-stable JSONL file under ``artifacts/sweeps/`` (one
  ``{hash, cell, result}`` line per cell, canonical JSON) — CI diffs it
  against a checked-in baseline.  Wall-clock/cache metadata goes to a
  sidecar ``.meta.json`` so it never perturbs the diff.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import json
import multiprocessing
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

from repro.sweep.cache import DEFAULT_CACHE_DIR, SweepCache
from repro.sweep.cells import Cell, canonical_json, cell_hash, run_cell

__all__ = ["SweepOutcome", "run_cells", "DEFAULT_ARTIFACTS_DIR"]

DEFAULT_ARTIFACTS_DIR = os.path.join("artifacts", "sweeps")


@dataclasses.dataclass
class SweepOutcome:
    """Everything one ``run_cells`` call produced, in grid order."""

    name: str
    cells: List[Cell]
    hashes: List[str]
    results: List[Dict[str, Any]]  # grid order, parallel to ``cells``
    cached_count: int
    computed_count: int
    wall_s: float
    jsonl_path: Optional[str]

    @property
    def total(self) -> int:
        """Total cell count (cached + computed)."""
        return len(self.cells)


def _hold_jax_to_cpu() -> None:
    """Pool initializer: a worker's JAX (a ``dqn`` cell's learner) runs on
    the CPU.

    Oracle cells are host work, and an accelerator belongs to one process:
    the parent, which runs the batched cells, already holds it.  The
    environment variable covers a later ``import jax``; the config update
    covers a worker whose ``__main__`` (re-imported by spawn) imported jax
    already, since no JAX backend has started yet at this point.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_platforms", "cpu")


def run_cells(
    name: str,
    cells: Sequence[Cell],
    *,
    workers: int = 0,
    cache: Union[SweepCache, str, None, bool] = True,
    resume: bool = True,
    artifacts_dir: Optional[str] = DEFAULT_ARTIFACTS_DIR,
    policy_factory: Optional[Callable[[], Any]] = None,
    progress: Optional[Callable[[str], None]] = None,
) -> SweepOutcome:
    """Run a grid of cells; returns results in grid order.

    ``cache``: True -> default dir, a str -> that dir, a SweepCache -> as-is,
    False/None -> no memoization.  ``resume=False`` ignores existing entries
    (recompute everything) but still persists fresh results.

    ``policy_factory`` forces inline execution with an ad-hoc policy and
    bypasses the cache entirely: an arbitrary closure is neither picklable
    nor content-addressable.
    """
    if isinstance(cache, bool):
        cache_obj = SweepCache(DEFAULT_CACHE_DIR) if cache else None
    elif isinstance(cache, str):
        cache_obj = SweepCache(cache)
    else:
        cache_obj = cache
    if policy_factory is not None:
        cache_obj = None

    if cache_obj is not None and resume:
        # refuse to resume over a cache written under a different SIM_VERSION
        # (raises StaleCacheError) — silent semantics-mixing is the one
        # failure mode a content-addressed cache cannot flag per-cell
        cache_obj.check_version()

    t0 = time.perf_counter()  # lint: waive[DT002] meta.json wall_s telemetry only
    cells = list(cells)
    hashes = [cell_hash(c) for c in cells]
    results: List[Optional[Dict[str, Any]]] = [None] * len(cells)

    cached_count = 0
    pending: List[int] = []
    for i, h in enumerate(hashes):
        hit = cache_obj.get(h) if (cache_obj is not None and resume) else None
        if hit is not None:
            results[i] = hit
            cached_count += 1
        else:
            pending.append(i)

    if progress and cells:
        progress(
            f"[{name}] {len(cells)} cells: {cached_count} cached, "
            f"{len(pending)} to compute (workers={max(workers, 1)})"
        )
    computed_count = len(pending)

    # batched-backend cells never enter the worker pool: grouping seeds into
    # one vectorized simulate_batch call *is* their parallelism, and keeping
    # jax in the parent avoids paying its import in every spawned worker.
    # (with an ad-hoc policy_factory they fall through to run_cell, which
    # rejects the combination with a useful error.)
    batched = [
        i for i in pending if cells[i].get("backend") == "batched"
    ] if policy_factory is None else []
    if batched:
        from repro.sweep.batched import run_batched_cells

        if progress:
            progress(f"[{name}] {len(batched)} batched cells run in-process")
        for i, out in zip(batched, run_batched_cells([cells[i] for i in batched]), strict=True):
            results[i] = out
            if cache_obj is not None:
                cache_obj.put(hashes[i], cells[i], out)
        done_batched = set(batched)
        pending = [i for i in pending if i not in done_batched]

    if pending:
        if policy_factory is not None or workers <= 1:
            for i in pending:
                out = run_cell(cells[i], policy_factory=policy_factory)
                results[i] = out
                if cache_obj is not None:
                    cache_obj.put(hashes[i], cells[i], out)
        else:
            max_workers = min(workers, os.cpu_count() or workers, len(pending))
            # spawn, not fork: the parent frequently has jax (and its thread
            # pools) loaded — forking a multithreaded process can deadlock.
            # Workers only import the numpy-based core, so spawn stays cheap.
            ctx = multiprocessing.get_context("spawn")
            with concurrent.futures.ProcessPoolExecutor(
                max_workers=max_workers, mp_context=ctx,
                initializer=_hold_jax_to_cpu,
            ) as ex:
                futs = {ex.submit(run_cell, cells[i]): i for i in pending}
                done = 0
                for fut in concurrent.futures.as_completed(futs):
                    i = futs[fut]
                    try:
                        out = fut.result()
                    except Exception as e:
                        raise RuntimeError(
                            f"sweep cell failed: {canonical_json(cells[i])}"
                        ) from e
                    results[i] = out
                    if cache_obj is not None:
                        cache_obj.put(hashes[i], cells[i], out)
                    done += 1
                    if progress and done % 50 == 0:
                        progress(f"[{name}] {done}/{len(pending)} computed")

    jsonl_path = None
    if artifacts_dir is not None:
        os.makedirs(artifacts_dir, exist_ok=True)
        jsonl_path = os.path.join(artifacts_dir, f"{name}.jsonl")
        tmp = jsonl_path + ".tmp"
        with open(tmp, "w") as f:
            for h, cell, result in zip(hashes, cells, results, strict=True):
                f.write(canonical_json({"hash": h, "cell": cell, "result": result}))
                f.write("\n")
        os.replace(tmp, jsonl_path)
        wall_s = time.perf_counter() - t0  # lint: waive[DT002] meta.json telemetry only
        with open(os.path.join(artifacts_dir, f"{name}.meta.json"), "w") as f:
            json.dump(
                {
                    "name": name,
                    "cells": len(cells),
                    "cached": cached_count,
                    "computed": computed_count,
                    "workers": workers,
                    "wall_s": wall_s,
                },
                f,
                indent=2,
            )
    else:
        wall_s = time.perf_counter() - t0  # lint: waive[DT002] meta.json telemetry only

    return SweepOutcome(
        name=name,
        cells=cells,
        hashes=hashes,
        results=results,  # type: ignore[arg-type]
        cached_count=cached_count,
        computed_count=computed_count,
        wall_s=wall_s,
        jsonl_path=jsonl_path,
    )
