"""Fit the simulator's cost scales + overlap constants from measured
step times.

Reference: every per-op cost in the reference search is a real kernel
measurement (inner_measure_operator_cost, model.cu:38-75), but its
comm/compute OVERLAP treatment is baked into the event simulation.
This module closes the same gap for the analytic path: the
`overlap_fraction` (how much parallel-op comm hides behind compute) and
`sync_overlap_fraction` (how much gradient sync hides behind backward)
were hand-set heuristics (0.3 / 0.7, pcg/unity.py:90-107, VERDICT r03
Weak #4).  Here the full prediction

    measured(s) ~= c·compute(s) + u·comm(s) + v·sync(s)

is least-squares fit over the SAME model compiled under different
strategies (single-device anchors c; dp / dp x tp / tp separate u and
v).  c calibrates the cost model's roofline to the live backend (the
role per-op measurement plays on-chip); u and v generalize
(1-overlap_fraction) / (1-sync_overlap_fraction) — they also absorb any
machine-model bandwidth error, which is exactly right for a constant
consumed by the same machine model during search ranking.  Fitted
values persist beside the op-cost cache and are picked up by the search
entry points (unity_optimize / mcmc_optimize) in later runs.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as np


def fit_cost_scales(
    records: Sequence[Tuple[float, float, float, float]],
) -> Dict[str, float]:
    """records: (measured_total, compute, comm, sync) seconds per
    strategy.  Solves nonneg least squares for (c, u, v); returns the
    scales plus the equivalent overlap constants (of = 1-u, sof = 1-v,
    may be negative when the machine model underestimates comm) and the
    mean relative prediction error after the fit."""
    A = np.asarray([[r[1], r[2], r[3]] for r in records], np.float64)
    b = np.asarray([r[0] for r in records], np.float64)
    x = np.array([1.0, 0.7, 0.3])  # priors: c=1, u=1-of, v=1-sof
    usable = np.abs(A).sum(axis=0) > 0
    if usable.any():
        sol, *_ = np.linalg.lstsq(A[:, usable], b, rcond=None)
        x[usable] = np.maximum(sol, 0.0)
    pred = A @ x
    rel = np.abs(pred - b) / np.maximum(b, 1e-12)
    return {
        "compute_scale": float(x[0]),
        "comm_scale": float(x[1]),
        "sync_scale": float(x[2]),
        "overlap_fraction": float(1.0 - x[1]),
        "sync_overlap_fraction": float(1.0 - x[2]),
        "mean_rel_error": float(rel.mean()),
        "max_rel_error": float(rel.max()),
        "num_strategies": len(records),
    }


def measure_step_time(ff, inputs, labels, iters: int = 12,
                      windows: int = 3) -> float:
    """Best-of-N windows of serial steps with ONE hard sync each: the
    steps chain on-device and the host waits once, so launch latency
    stays out of the per-step number."""
    for _ in range(2):
        m = ff.train_step(inputs, labels)
    _ = float(m["loss"])

    def window():
        t0 = time.perf_counter()
        for _ in range(iters):
            m = ff.train_step(inputs, labels)
        _ = float(m["loss"])
        return (time.perf_counter() - t0) / iters

    return min(window() for _ in range(windows))


def simulate_components(ff, strategy, machine,
                        cost_model) -> Tuple[float, float, float]:
    """(compute, comm, sync) seconds the simulator attributes to the
    compiled model under `strategy` — the regressors of the fit."""
    from .simulator import Simulator

    sim = Simulator(machine, cost_model)
    res = sim.simulate(ff.operators, strategy.mesh_axes, training=True)
    return res.compute_time, res.comm_time, res.sync_time


def calibrate_overlap(
    build, strategies, devices, machine, cost_model,
    make_inputs, iters: int = 12, windows: int = 3,
) -> Dict[str, float]:
    """Compile `build()` under each (strategy, num_devices) pair,
    measure real step time, simulate its analytic components, and fit.

    build() -> a fresh un-compiled FFModel with layers added.
    strategies: [(Strategy, n_devices)] — include a single-device entry
        (comm=sync=0) so the compute scale is anchored.
    make_inputs(ff) -> (inputs dict, labels) device-put for ff.
    """
    from .. import SGDOptimizer

    records = []
    for s, n in strategies:
        ff = build()
        ff.compile(optimizer=SGDOptimizer(lr=0.01), strategy=s,
                   devices=devices[:n])
        inputs, labels = make_inputs(ff)
        measured = measure_step_time(ff, inputs, labels, iters, windows)
        compute, comm, sync = simulate_components(ff, s, machine, cost_model)
        records.append((measured, compute, comm, sync))
    fit = fit_cost_scales(records)
    # constants are backend-specific (a CPU-mesh compute_scale is ~200x
    # a chip's); loaders refuse mismatched backends
    fit["fitted_on"] = devices[0].platform if devices else "unknown"
    return fit


# -- persistence (beside the op-cost cache) --------------------------------

def overlap_constants_path() -> str:
    base = os.environ.get("FLEXFLOW_TPU_CACHE_DIR",
                          os.path.expanduser("~/.cache/flexflow_tpu"))
    os.makedirs(base, exist_ok=True)
    return os.path.join(base, "overlap_constants.json")


def save_overlap_constants(fit: Dict[str, float],
                           path: Optional[str] = None) -> str:
    path = path or overlap_constants_path()
    with open(path, "w") as f:
        json.dump(fit, f, indent=1)
    return path


def load_overlap_constants(path: Optional[str] = None,
                           backend: Optional[str] = None) -> Optional[Dict]:
    """Returns the fitted constants only when their recorded backend
    matches the one in use (default: jax's current backend) — a
    CPU-mesh compute_scale applied on a chip would corrupt every search
    ranking."""
    path = path or overlap_constants_path()
    try:
        with open(path) as f:
            d = json.load(f)
    except (OSError, ValueError):
        return None
    # sanity: scales nonnegative and finite
    try:
        ok = (np.isfinite(d["compute_scale"]) and d["compute_scale"] >= 0
              and np.isfinite(d["comm_scale"]) and d["comm_scale"] >= 0
              and np.isfinite(d["sync_scale"]) and d["sync_scale"] >= 0)
    except (KeyError, TypeError):
        return None
    if not ok:
        return None
    if backend is None:
        try:
            import jax

            backend = jax.default_backend()
        except Exception:
            return None
    if d.get("fitted_on") != backend:
        return None
    return d
