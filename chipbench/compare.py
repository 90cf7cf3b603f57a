"""The comparison that decides ``correct``, and the parity helpers the
benchmark keeps as its own copy (from ``chip_smoke.py``: ``first_diff``,
the delegation check and the proof that a vmapped tile was dispatched).

Four numbers are compared, each against its limit (``LIMITS``):

- ``provision_mismatch``: fields of the jobs and CI values the program
  materialised that differ from the benchmark's own generator;
- ``decision_mismatch``: per-job completion slot, waiting slots,
  violation flag, per-slot servers used, jobs running and queued and
  the learned-state count that differ from
  the plain reference;
- ``energy_rel_err`` / ``carbon_rel_err``: the largest relative gap of
  a total or a per-slot value of energy / carbon.
"""
from __future__ import annotations

import math

import numpy as np

#: Limits, each set between the program's readings over a dozen seeds and
#: the float32 control's (PERF.md, "How correct is decided").  The two
#: counts are exact comparisons.
LIMITS = {
    "provision_mismatch": 0,
    "decision_mismatch": 0,
    "energy_rel_err": 1e-9,
    "carbon_rel_err": 1e-9,
    # the run itself: nothing compiles in the window, no case leaves the
    # device path, and a sweep dispatches vmapped tiles
    "window_compiles": 0,
    "delegated_cases": 0,
    "tile_programs_missing": 0,
}
_ERRS = ("energy_rel_err", "carbon_rel_err")


def first_diff(a, b, path: str = "") -> str | None:
    """Where two nested dict/list trees first differ, or None if equal."""
    if isinstance(a, dict) and isinstance(b, dict):
        if a.keys() != b.keys():
            return f"{path}: keys {sorted(a.keys() ^ b.keys())} differ"
        for k in a:
            d = first_diff(a[k], b[k], f"{path}.{k}")
            if d:
                return d
        return None
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        if len(a) != len(b):
            return f"{path}: length {len(a)} != {len(b)}"
        for i, (x, y) in enumerate(zip(a, b)):
            d = first_diff(x, y, f"{path}[{i}]")
            if d:
                return d
        return None
    return None if a == b else f"{path}: {a!r} != {b!r}"


def _rel(a: float, b: float) -> float:
    if a == b:
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-300)


_JOB_INT = ("completion", "violations")
_SLOT_INT = ("slot", "provisioned", "used", "running", "queued")


class Tally:
    """Accumulates the compared numbers over every checked result."""

    def __init__(self) -> None:
        self.values = {"provision_mismatch": 0, "decision_mismatch": 0,
                       "energy_rel_err": 0.0, "carbon_rel_err": 0.0}
        self.notes: list[str] = []

    def merge(self, other: "Tally") -> None:
        for k, v in other.values.items():
            self.values[k] = max(self.values[k], v) if k in _ERRS \
                else self.values[k] + v
        for note in other.notes:
            self._note(note)

    def count(self, key: str, n: int) -> None:
        """A run-level count held against its limit."""
        self.values[key] = n
        if n > LIMITS[key]:
            self._note(f"{key}: {n}")

    def _note(self, msg: str) -> None:
        if len(self.notes) < 8:
            self.notes.append(msg)

    def provision(self, n_bad: int, what: str) -> None:
        if n_bad:
            self.values["provision_mismatch"] += n_bad
            self._note(f"provision {what}: {n_bad} mismatches")

    def decisions(self, n_bad: int, what: str) -> None:
        if n_bad:
            self.values["decision_mismatch"] += n_bad
            self._note(f"decisions {what}: {n_bad} mismatches")

    def _err(self, key: str, a, b, what: str) -> None:
        e = _rel(float(a), float(b))
        if e > self.values[key]:
            self.values[key] = e
            if e > LIMITS[key]:
                self._note(f"{key} {what}: {a!r} vs {b!r}")

    def result(self, prog: dict, ref: dict, what: str) -> None:
        """Compare one program result (``SimResult.to_dict`` with per-job
        and per-slot detail) with the reference's."""
        bad = int(prog.get("policy") != ref["policy"])
        for key in _JOB_INT + ("wait_slots",):
            if key not in ref:
                continue
            pa, ra = prog.get(key), ref[key]
            if pa is None or len(pa) != len(ra):
                bad += len(ra)
                continue
            bad += sum(1 for x, y in zip(pa, ra) if x != y)
        bad += int(prog.get("num_jobs") != ref["num_jobs"])
        ps, rs = prog["slots"], ref["slots"]
        bad += abs(len(ps) - len(rs)) * len(_SLOT_INT)
        for p, r in zip(ps, rs):
            bad += sum(1 for k in _SLOT_INT if p[k] != r[k])
            self._err("energy_rel_err", p["energy_kwh"], r["energy_kwh"],
                      f"{what} slot {r['slot']}")
            self._err("carbon_rel_err", p["carbon_g"], r["carbon_g"],
                      f"{what} slot {r['slot']}")
        if bad:
            keys = [k for k in _JOB_INT + ("wait_slots", "policy", "slots")
                    if k in ref]
            where = first_diff({k: prog.get(k) for k in keys},
                               {k: ref[k] for k in keys})
            self.decisions(bad, f"{what} (first at {where})")
        self._err("energy_rel_err", prog["energy_kwh"], ref["energy_kwh"],
                  f"{what} total")
        self._err("carbon_rel_err", prog["carbon_g"], ref["carbon_g"],
                  f"{what} total")

    def passed(self) -> bool:
        return all(v <= LIMITS[k] for k, v in self.values.items())

    def lines(self) -> list[str]:
        return [f"{k} {v!r} limit {LIMITS[k]!r}"
                for k, v in self.values.items()]

    def as_json(self) -> dict:
        return {k: {"value": v, "limit": LIMITS[k]}
                for k, v in self.values.items()}


def world_mismatch(mat, world) -> tuple[int, str]:
    """Fields of the program's materialised world (a
    ``MaterializedScenario``) that differ from the benchmark's own
    generator: every job's id, arrival, length, queue, slack, k_min,
    power, communication size and profile, and every CI value."""
    bad = 0
    pj, rj = mat.jobs, world.jobs
    bad += abs(len(pj) - len(rj))
    for a, b in zip(pj, rj):
        bad += sum(1 for x, y in ((a.job_id, b.job_id), (a.arrival, b.arrival),
                                  (a.length, b.length), (a.queue, b.queue),
                                  (a.delay, b.delay), (a.k_min, b.k_min),
                                  (a.power, b.power),
                                  (a.comm_size, b.comm_size)) if x != y)
        if not np.array_equal(np.asarray(a.profile), b.profile):
            bad += 1
    bad += abs(len(mat.eval_jobs) - len(world.eval_jobs))
    traces = [np.asarray(mat.ci.trace)]
    bad += abs(len(traces) - len(world.traces))
    for p, r in zip(traces, world.traces):
        if p.shape != r.shape:
            bad += max(len(p), len(r))
        else:
            bad += int(np.count_nonzero(p != r))
    return bad, f"{len(rj)} jobs, {len(world.traces)} CI trace(s)"


def digest(out: dict, plan) -> dict:
    """What the check keeps of one request's output: its index, the
    compared results as dicts, the result count, and the run's scenario
    and knowledge-base size (None for a sweep)."""
    res = out["results"]
    return {"compared": plan.compared(out["n"]),
            "results": {i: res[i].to_dict(include_per_job=True,
                                          include_slots=True)
                        for i in plan.compared(out["n"]) if i < len(res)},
            "n_results": len(res), "scenario": out["scenario"],
            "kb_size": out["kb_size"]}


def check_request(d: dict, plan, ref, what: str = "") -> Tally:
    """The compared numbers of one request (a ``digest``) against the
    reference (a ``reference.Reference`` of the same plan)."""
    tally = Tally()
    if d["n_results"] != plan.units_per_request:
        tally.decisions(abs(plan.units_per_request - d["n_results"]),
                        f"{what} result count")
    for i in d["compared"]:
        if i in d["results"]:
            tally.result(d["results"][i], ref.result(i), f"{what} cell {i}")
    world = plan.worlds[0]
    if d["scenario"] is not None:
        bad, where = world_mismatch(d["scenario"].materialize(),
                                    ref.world(world))
        tally.provision(bad, f"{what} {where}")
    if d["kb_size"] is not None and \
            d["kb_size"] != ref.learned_states(world):
        tally.decisions(1, f"{what} learned states")
    return tally
