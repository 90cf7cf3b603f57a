"""The one general generator: a configuration file and a traffic file in,
a plan of identical requests out.

A configuration (``configs/<name>.json``) fixes the deployment: the
``scenario`` fields every world shares, the ``policies`` an operator's
what-if query evaluates, and the band of jobs an evaluated week holds.
A traffic mix (``traffic/<name>.json``) fixes what one request asks:

- ``"call": "run"``: one ``run()`` of the configuration's policies over
  one world; with ``worlds_per_run: W`` the run draws W worlds and
  request ``n`` uses world ``n mod W``, so a window's rate averages a
  fixed cycle of worlds and not one world's work;
- ``"call": "sweep"``: one ``Sweep.run()`` over ``sweep_regions`` x
  ``seeds_per_request`` worlds x an optional noisy ``forecasts`` grid x
  its own ``policies``.

Any traffic key named like a ``scenario`` field (``learn_weeks``, ...)
overrides the configuration's.  A ``scenario`` with ``regions`` (two or
more) is a geo-distributed deployment: its worlds span those regions,
and its ``migration`` costs, when given, go to every ``Scenario``.  A
``scenario`` with ``dag`` (the fields of the program's ``DagConfig``) is
a DAG deployment: its worlds' jobs are the tasks of whole DAGs, gated on
their predecessors, and its band counts evaluated tasks.
World seeds, forecast seeds and the cells the check compares all derive
from ``--seed``; a sweep's compared cells are drawn anew for every
request, so a window's check covers most of the grid.  A world seed is
kept only when its evaluated weeks hold a job count inside the
configuration's ``eval_jobs_band`` (the central 80% of the generator's
own distribution), so seeds do like amounts of work.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .reference import world as rworld

_SCENARIO_KEYS = ("family", "capacity", "utilization", "learn_weeks",
                  "eval_weeks")


@dataclasses.dataclass
class WorldSpec:
    """One world of a request: where, which seed, which forecast."""

    regions: tuple[str, ...]
    seed: int
    family: str
    capacity: int
    utilization: float
    learn_weeks: int
    eval_weeks: int
    forecast: tuple[float, int] | None = None      # (sigma, seed) if noisy

    @property
    def is_geo(self) -> bool:
        """A world over two or more regions is one geo cluster."""
        return len(self.regions) > 1

    def scenario_kwargs(self) -> dict:
        kw = dict(family=self.family, capacity=self.capacity,
                  utilization=self.utilization,
                  learn_weeks=self.learn_weeks, eval_weeks=self.eval_weeks,
                  seed=self.seed)
        if self.is_geo:
            kw["regions"] = self.regions
        else:
            kw["region"] = self.regions[0]
        return kw

    def world_kwargs(self) -> dict:
        return dict(regions=self.regions, family=self.family,
                    capacity=self.capacity, utilization=self.utilization,
                    learn_weeks=self.learn_weeks, eval_weeks=self.eval_weeks,
                    seed=self.seed)


@dataclasses.dataclass
class Plan:
    """What every request of a run does, and what the check compares."""

    call: str                       # "run" | "sweep"
    worlds: list[WorldSpec]         # run: its cycle; sweep: Sweep's order
    policies: tuple[str, ...]
    units_per_request: int          # policy-weeks (run) or cells (sweep)
    n_compare: int                  # results of a request compared
    unit: str
    seed: int
    migration: dict | None = None   # MigrationModel fields of a geo world
    dag: dict | None = None         # DagConfig fields of a DAG world

    @property
    def cycle(self) -> int:
        """Requests that together cover every world once."""
        return len(self.worlds) if self.call == "run" else 1

    def world_index(self, i: int, n: int = 0) -> int:
        """Index in ``worlds`` of result ``i`` of the ``n``-th request: a
        run cycles its worlds request by request; a sweep's results are
        worlds outer, policies inner, as ``Sweep`` orders them."""
        if self.call == "run":
            return n % len(self.worlds)
        return i // len(self.policies)

    def compared(self, n: int) -> list[int]:
        """Result indices the check compares in the run's ``n``-th
        request: all of them, or a draw from ``(seed, n)``."""
        if self.n_compare >= self.units_per_request:
            return list(range(self.units_per_request))
        rng = np.random.default_rng(np.random.SeedSequence(
            [int(self.seed), 3, int(n)]))
        return sorted(int(i) for i in rng.choice(
            self.units_per_request, size=self.n_compare, replace=False))

    def cell(self, i: int, n: int = 0) -> tuple[WorldSpec, str]:
        """World and policy of result ``i`` of the ``n``-th request."""
        return self.worlds[self.world_index(i, n)], \
            self.policies[i % len(self.policies)]


def _rng(seed: int, purpose: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed),
                                                         purpose]))


def _world_seeds(n: int, seed: int, scen: dict, band) -> list[int]:
    """``n`` distinct world seeds from ``seed`` whose evaluated weeks hold
    a job (DAG: task) count inside ``band`` (inclusive); any seed when
    band is None."""
    rng = _rng(seed, 1)
    out: list[int] = []
    for _ in range(200 * n):
        s = int(rng.integers(0, 2 ** 31 - 2))
        if s in out:
            continue
        if band is not None:
            c = rworld.eval_job_count(
                family=scen["family"], capacity=scen["capacity"],
                utilization=scen["utilization"],
                learn_weeks=scen["learn_weeks"],
                eval_weeks=scen["eval_weeks"], seed=s, dag=scen.get("dag"))
            if not band[0] <= c <= band[1]:
                continue
        out.append(s)
        if len(out) == n:
            return out
    raise RuntimeError(f"no {n} world seeds with an evaluated-week job "
                       f"count in {band} from seed {seed}")


def make_plan(config: dict, traffic: dict, seed: int) -> Plan:
    scen = dict(config["scenario"])
    for k in _SCENARIO_KEYS:
        if k in traffic:
            scen[k] = traffic[k]
    scen.setdefault("family", "azure")
    scen.setdefault("utilization", 0.5)
    scen.setdefault("eval_weeks", 1)
    band = config.get("eval_jobs_band")
    call = traffic["call"]

    def spec(regions, s, fc=None):
        return WorldSpec(regions=tuple(regions), seed=s,
                         family=scen["family"], capacity=scen["capacity"],
                         utilization=scen["utilization"],
                         learn_weeks=scen["learn_weeks"],
                         eval_weeks=scen["eval_weeks"], forecast=fc)

    geo = "regions" in scen
    if geo:
        home = tuple(scen["regions"])
        if len(home) < 2:
            raise ValueError("a geo deployment needs two or more regions")
    else:
        home = (scen["region"],)
    if call == "run":
        policies = tuple(traffic.get("policies", config["policies"]))
        worlds = [spec(home, s) for s in _world_seeds(
            traffic.get("worlds_per_run", 1), seed, scen, band)]
        units = len(policies) * scen["eval_weeks"]
        n_cmp = units
        unit = "policy-weeks"
    elif call == "sweep":
        if geo or "dag" in scen:
            raise ValueError("a sweep takes single-region worlds of "
                             "independent jobs")
        policies = tuple(traffic["policies"])
        regions = tuple(traffic.get("sweep_regions", home))
        seeds = _world_seeds(traffic.get("seeds_per_request", 1), seed,
                             scen, band)
        fcs: list = [None]
        if "forecasts" in traffic:
            f = traffic["forecasts"]
            fseeds = [int(x) for x in _rng(seed, 2).integers(
                0, 2 ** 31 - 2, size=f["seeds"])]
            fcs = [(float(sig), fs) for fs in fseeds for sig in f["sigmas"]]
        worlds = [spec((r,), s, fc) for r in regions for s in seeds
                  for fc in fcs]
        units = len(worlds) * len(policies)
        n_cmp = min(traffic.get("compare_cells", units), units)
        unit = "cells"
    else:
        raise ValueError(f"unknown traffic call {call!r}")
    return Plan(call=call, worlds=worlds, policies=policies,
                units_per_request=units, n_compare=n_cmp, unit=unit,
                seed=seed, migration=scen.get("migration"),
                dag=scen.get("dag"))


class Requests:
    """Builds and sends the request of a plan to the program: fresh
    ``Scenario`` objects every time, so each request materialises its
    worlds and learns as a user's call does."""

    def __init__(self, plan: Plan):
        from repro.core.forecast import NoisyForecast
        from repro.core.types import MigrationModel
        from repro.experiment import Scenario, Sweep, run
        from repro.traces import DagConfig

        self.plan = plan
        self._Scenario, self._Sweep, self._run = Scenario, Sweep, run
        self._Noisy = NoisyForecast
        self._extra = {}
        if plan.migration is not None:
            self._extra["migration"] = MigrationModel(**plan.migration)
        if plan.dag is not None:
            self._extra["dag"] = DagConfig(**plan.dag)
        self._sent = 0

    def send(self, telemetry=None) -> dict:
        """The next request.  Returns its serial number ``n`` in the run,
        its ``results`` in plan order and, for a ``run()``, the
        ``scenario`` it materialised and its ``kb_size``."""
        p = self.plan
        n = self._sent
        self._sent += 1
        if p.call == "run":
            sc = self._Scenario(engine="scan", **self._extra,
                                **p.worlds[p.world_index(0, n)]
                                .scenario_kwargs())
            res = self._run(sc, p.policies, telemetry=telemetry)
            return {"n": n, "results": [r for pol in p.policies
                                        for r in res.weekly[pol]],
                    "scenario": sc, "kb_size": res.kb_size}
        w0 = p.worlds[0]
        regions = list(dict.fromkeys(w.regions[0] for w in p.worlds))
        seeds = list(dict.fromkeys(w.seed for w in p.worlds))
        fcs = list(dict.fromkeys(w.forecast for w in p.worlds))
        base = self._Scenario(engine="scan", **w0.scenario_kwargs())
        sweep = self._Sweep(
            base=base, regions=regions, seeds=seeds, policies=p.policies,
            forecasts=None if fcs == [None] else
            [self._Noisy(sigma=sig, seed=fs) for sig, fs in fcs],
            telemetry=telemetry)
        res = sweep.run()
        return {"n": n, "results": list(res.results), "scenario": None,
                "kb_size": None}
