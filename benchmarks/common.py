"""Shared benchmark harness over the ``repro.experiment`` API.

Scale note: the paper's evaluation uses 150-server clusters and week-long
traces with year-long simulator sweeps.  The benchmarks reproduce every
figure's *comparison* at a CI-friendly scale (capacity 60, 3 learning
weeks + 1 evaluation week — the experiment ``Scenario`` defaults); pass
``--full`` to run the paper's scale.  Results are cached as JSON under
results/bench/.
"""
from __future__ import annotations

import json
import os
import time

from repro.experiment import Scenario, run as run_experiment

WEEK = 24 * 7
RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results", "bench")

__all__ = ["Scenario", "run_policies", "cached", "csv_rows", "WEEK"]


def run_policies(sc: Scenario, policies: list[str] | None = None) -> dict:
    """Run the named registry policies on the scenario through the
    experiment driver; returns the per-policy metric dicts the figure
    caches store.  Per-policy runtimes are not reported: the driver
    evaluates all policies in one batched ``simulate_many`` dispatch
    (``cached`` records the figure-level wall time as ``_runtime_s``)."""
    return run_experiment(sc, policies).metrics()


def cached(name: str, fn, force: bool = False):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, name + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    t0 = time.time()
    res = fn()
    res["_runtime_s"] = round(time.time() - t0, 1)
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    return res


def csv_rows(bench: str, res: dict) -> list[str]:
    """Flatten a benchmark result into `name,us_per_call,derived` CSV rows."""
    rows = []
    for key, metrics in res.items():
        if key.startswith("_"):
            continue
        if isinstance(metrics, dict) and "carbon_g" in metrics:
            us = metrics.get("runtime_s", 0) * 1e6
            rows.append(f"{bench}/{key},{us:.0f},"
                        f"savings={metrics.get('savings_pct', 0)}%"
                        f";wait={metrics['mean_wait_h']:.1f}h"
                        f";viol={metrics['violation_rate']:.3f}")
        elif isinstance(metrics, dict):
            for sub, m2 in metrics.items():
                if isinstance(m2, dict) and "carbon_g" in m2:
                    rows.append(f"{bench}/{key}/{sub},"
                                f"{m2.get('runtime_s', 0) * 1e6:.0f},"
                                f"savings={m2.get('savings_pct', 0)}%")
    return rows
