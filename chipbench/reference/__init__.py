"""The plain reference the benchmark compares the program with: its own
world generator (``world``) and slot-loop simulation (``sim``).  It
imports nothing of the program."""
from __future__ import annotations

import numpy as np

from . import sim
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
        self._worlds: dict = {}
        self._results: dict[int, dict] = {}

    def world(self, ws):
        key = (ws.regions, ws.seed)
        if key not in self._worlds:
            if ws.eval_weeks != 1:
                raise ValueError("the reference evaluates one week")
            self._worlds[key] = build_world(**ws.world_kwargs())
        return self._worlds[key]

    @property
    def computed(self) -> list[int]:
        """Result indices simulated so far."""
        return list(self._results)

    def result(self, i: int) -> dict:
        if i not in self._results:
            self._results[i] = self._simulate(i)
        return self._results[i]

    def _simulate(self, i: int) -> dict:
        ws, name = self.plan.cell(i)
        w = self.world(ws)
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
