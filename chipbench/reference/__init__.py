"""The plain reference the benchmark compares the program with: its own
world generator (``world``) and slot-loop simulations (``sim`` for one
region, ``geo`` for a geo-distributed cluster, ``dag`` for tasks gated
on their predecessors).  It imports nothing of the program."""
from __future__ import annotations

import numpy as np

from . import dag, geo, sim
from .world import WEEK, Forecast, build_world

KB_POLICIES = ("carbonflex-mpc", "carbonflex-scale")


def _single_policy(name: str, world, learn_weeks: int, dtype):
    if name == "carbon-agnostic":
        return sim.CarbonAgnostic()
    if name == "wait-awhile":
        return sim.WaitAwhile()
    if name == "carbonflex-mpc":
        return sim.RecedingHorizon(world.hist, n_queues=3)
    if name == "carbonflex-scale":
        rho, _ = sim.learned_scale_rho(
            world.hist, np.asarray(world.traces[0], dtype=dtype),
            world.capacities[0], learn_weeks)
        return sim.RecedingHorizon(world.hist, n_queues=3, scale_rho=rho)
    raise ValueError(f"the reference has no single-region policy {name!r}")


class Reference:
    """Reference results for the cells of a plan, worlds built once."""

    def __init__(self, plan, dtype=np.float64):
        self.plan = plan
        self.dtype = dtype
        self.migration = geo.Migration(**(plan.migration or {}))
        self._worlds: dict = {}
        self._results: dict[tuple, dict] = {}

    def world(self, ws):
        key = (ws.regions, ws.seed)
        if key not in self._worlds:
            if ws.eval_weeks != 1:
                raise ValueError("the reference evaluates one week")
            self._worlds[key] = build_world(**ws.world_kwargs(),
                                            dag=self.plan.dag)
        return self._worlds[key]

    @property
    def computed(self) -> list[tuple]:
        """(world, policy) pairs simulated so far."""
        return list(self._results)

    def result(self, i: int, n: int = 0) -> dict:
        """The reference's result ``i`` of the ``n``-th request."""
        ws, name = self.plan.cell(i, n)
        key = (ws.regions, ws.seed, ws.forecast, name)
        if key not in self._results:
            self._results[key] = self._simulate(ws, name)
        return self._results[key]

    def _simulate(self, ws, name: str) -> dict:
        w = self.world(ws)
        if ws.is_geo:
            if name not in geo.POLICIES:
                raise ValueError(f"the reference has no geo policy {name!r}")
            return geo.simulate_geo(w.eval_jobs, w.traces, w.capacities,
                                    w.regions, geo.POLICIES[name](), w.t0,
                                    WEEK, self.migration, dtype=self.dtype)
        if self.plan.dag is not None:
            if name not in dag.POLICIES:
                raise ValueError(f"the reference has no DAG policy {name!r}")
            return dag.simulate_dag(w.eval_jobs, w.traces[0], Forecast(),
                                    w.capacities[0], dag.POLICIES[name](),
                                    w.t0, WEEK, dtype=self.dtype)
        fc = Forecast() if ws.forecast is None else Forecast(*ws.forecast)
        pol = _single_policy(name, w, ws.learn_weeks, self.dtype)
        return sim.simulate_single(w.eval_jobs, w.traces[0], fc,
                                   w.capacities[0], pol, w.t0, WEEK,
                                   dtype=self.dtype)

    def learned_states(self, ws) -> int:
        """States the learning phase stores: one per slot of each
        learning week that holds arrivals, when a policy needs them."""
        if not any(p in KB_POLICIES for p in self.plan.policies):
            return 0
        w = self.world(ws)
        return sum(WEEK for k in range(ws.learn_weeks)
                   if any(k * WEEK <= j.arrival < (k + 1) * WEEK
                          for j in w.hist))
