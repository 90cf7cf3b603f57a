"""Dev harness: MPC knob-grid tuner for the receding-horizon policies.

Grids :class:`MPCConfig` knobs (horizon, replan cadence, length
percentile, clean-window fraction) through one shared world: the
scenario is materialized and its knowledge base learned exactly once,
then every knob combination becomes one scan-engine ``SimCase`` in a
single ``simulate_many`` batch — structurally identical cells fuse into
vmapped device programs, so the whole grid is a handful of device
dispatches rather than a grid of full runs.

The printed gap is measured against the oracle run in the same batch;
the reference rows (carbon-agnostic / greedy carbonflex / oracle) anchor
the numbers.  This is the harness that picked the shipped
``MPCConfig()`` defaults.

Usage: PYTHONPATH=src python scripts/tune_policy.py [--quick] [--scale]
"""
import dataclasses
import itertools
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache
from repro.core.mpc import MPCConfig
from repro.core.simulator import SimCase, simulate_many
from repro.experiment import Scenario
from repro.experiment.driver import prepare_context
from repro.experiment.registry import make_policy
from repro.experiment.scenario import WEEK

REFS = ("carbon-agnostic", "carbonflex", "oracle")


def default_grid(scale: bool):
    """The knob grid: horizon x replan cadence x length percentile, plus
    the clean-window fraction axis when tuning ``carbonflex-scale``."""
    horizons = (24, 48, 72)
    replans = (1, 6)
    percentiles = (75.0, 85.0, 95.0)
    cleans = (0.15, 0.25, 0.4) if scale else (0.25,)
    return [MPCConfig(horizon=h, replan_every=r, percentile=p, clean_frac=c)
            for h, r, p, c in itertools.product(horizons, replans,
                                                percentiles, cleans)]


def tune(policy="carbonflex-mpc", grid=None, region="south-australia",
         seed=1, capacity=40, learn_weeks=2, scale=False):
    if grid is None:
        grid = default_grid(scale)
    sc = Scenario(region=region, capacity=capacity, learn_weeks=learn_weeks,
                  seed=seed, engine="scan")
    mat = sc.materialize()
    names = REFS + (policy,)
    ctx = prepare_context(mat, names)
    horizon = sc.eval_weeks * WEEK

    def case(name, pctx, label):
        return SimCase(jobs=mat.eval_jobs, ci=mat.ci, cluster=mat.cluster,
                       policy=make_policy(name, pctx), t0=mat.t0,
                       horizon=horizon, engine="scan", label=label)

    cases = [case(n, ctx, n) for n in REFS]
    labels = list(REFS)
    for cfg in grid:
        lab = (f"H={cfg.horizon:<3d} R={cfg.replan_every} "
               f"p{cfg.percentile:g}"
               + (f" cf={cfg.clean_frac:g}" if scale else ""))
        cases.append(case(policy, dataclasses.replace(ctx, mpc=cfg), lab))
        labels.append(lab)
    results = simulate_many(cases)      # one batched scan dispatch

    by = dict(zip(labels, results))
    base = by["carbon-agnostic"].carbon_g
    orc_sv = 100.0 * (1.0 - by["oracle"].carbon_g / base)
    print(f"[{policy} | {region} seed={seed} cap={capacity}] "
          f"oracle {orc_sv:6.2f}%")
    out = {}
    for lab in labels:
        r = by[lab]
        sv = 100.0 * (1.0 - r.carbon_g / base)
        out[lab] = orc_sv - sv
        print(f"  {lab:24s} savings {sv:6.2f}%  gap {orc_sv - sv:6.2f}pp"
              f"  wait {r.mean_wait:5.1f}  viol {r.violation_rate:.3f}")
    best = min((lab for lab in labels if lab not in REFS), key=out.get)
    print(f"  -> best: {best}  (gap {out[best]:.2f}pp)")
    return out


if __name__ == "__main__":
    enable_compile_cache()
    quick = "--quick" in sys.argv
    scale = "--scale" in sys.argv
    policy = "carbonflex-scale" if scale else "carbonflex-mpc"
    grid = None
    if quick:
        grid = [MPCConfig(horizon=h, percentile=p)
                for h in (24, 48) for p in (75.0, 85.0)]
    for seed in ([1] if quick else [1, 3]):
        tune(policy=policy, grid=grid, seed=seed, scale=scale,
             capacity=20 if quick else 40,
             learn_weeks=1 if quick else 2)
