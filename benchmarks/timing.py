"""Shared steady-state measurement protocol (BASELINE.md step 2:
single-run numbers disagreed with their notes by more than the
run-to-run variance).

``median_throughput`` runs a warm, self-syncing closure N times and
reports the MEDIAN rate plus min/max, so the committed artifact is
robust to run-to-run jitter and matches what the notes claim."""
from __future__ import annotations

import statistics
import time
from typing import Callable, Dict


def median_throughput(run_once: Callable[[], None], units_per_run,
                      n_trials: int = 5) -> Dict[str, float]:
    """``run_once`` must execute the full measured work AND sync on a
    computed scalar (not just block_until_ready).  Returns
    {"value": median units/s, "min": ..., "max": ..., "n_trials": N}.
    """
    rates = []
    for _ in range(n_trials):
        t0 = time.perf_counter()
        run_once()
        dt = time.perf_counter() - t0
        rates.append(units_per_run / dt)
    rates.sort()
    return {"value": round(statistics.median(rates), 2),
            "min": round(rates[0], 2),
            "max": round(rates[-1], 2),
            "n_trials": n_trials}


def feed_stall_report(iterator, step_fn, *, pure_step_s: float,
                      n_batches: int) -> Dict[str, float]:
    """Input-pipeline stall accounting for one feeding strategy.

    Walks ``iterator`` for ``n_batches``, calling ``step_fn(ds)`` (which
    must sync on the step's result) per batch, and attributes everything
    that is not pure device compute to the input pipeline:

        host_wait = total_wall − n_batches × pure_step_s

    where ``pure_step_s`` is the same step measured on a device-resident
    batch. This charges the H2D copy to the pipeline even when it hides
    inside the jit dispatch (the host-async case), so sync /
    host-async / device-prefetch feeding are comparable on one scale.

    Returns ``{"total_s", "fetch_s", "host_wait_pct", "n_batches"}``;
    ``fetch_s`` is the explicit ``next()`` wait alone (the part a plain
    timer would see)."""
    if hasattr(iterator, "reset"):
        iterator.reset()
    fetch_s = 0.0
    t_start = time.perf_counter()
    for _ in range(n_batches):
        t0 = time.perf_counter()
        ds = iterator.next()
        fetch_s += time.perf_counter() - t0
        step_fn(ds)
    total_s = time.perf_counter() - t_start
    host_wait = max(0.0, total_s - n_batches * pure_step_s)
    return {"total_s": round(total_s, 4),
            "fetch_s": round(fetch_s, 4),
            "host_wait_pct": round(100.0 * host_wait / total_s, 2)
            if total_s > 0 else 0.0,
            "n_batches": n_batches}
