"""Plain reference of the geo-distributed cluster, written from the
semantics the program documents (``core/geo.py``, the geo slot loop of
``core/simulator.py``, ``core/types.py::MigrationModel``) and importing
none of it.

One slot at a time over per-region capacities and aligned CI traces:
admit the arrivals, each in its home region (row ``i`` of the
(arrival, job id)-sorted evaluated jobs arrives in region ``i mod R``);
let the policy return ``{row: (region, k)}``; a region that differs from
the job's own is a free placement before the job's first slot and a
migration after it (the job is suspended for ``Migration.slots`` slots,
counted from the slot it starts, and its transfer energy is billed at
the destination's CI on that slot); charge each region's energy at its
CI; advance progress, burn the slack of jobs left waiting (a migrating
job among them); retire finished jobs.  Every job runs at ``k_min``.
Every float is computed in ``dtype`` (see ``sim.py``).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .sim import _EPS, MAX_OVERRUN, POWER, SLOT_H, _Run, _slot_energy, _thr
from .world import WEEK, RJob, truth_slice

LOOKAHEAD = 24            # geo-flex's day-ahead forecast block, slots


@dataclasses.dataclass(frozen=True)
class Migration:
    """Cost of moving a started job between regions: a fixed
    checkpoint/restore time plus a share of its length, and a transfer
    energy per GB of its state (``comm_size``, floored at ``min_gb``)."""

    base_slots: int = 1
    slots_per_length: float = 0.02
    energy_kwh_per_gb: float = 0.05
    min_gb: float = 1.0

    def slots(self, job: RJob) -> int:
        return int(self.base_slots + np.ceil(self.slots_per_length
                                             * job.length))

    def energy_kwh(self, job: RJob, dtype):
        return dtype(self.energy_kwh_per_gb) * dtype(max(self.min_gb,
                                                         job.comm_size))


class _GeoRun(_Run):
    """A job inside the geo cluster: its region and migration countdown."""

    __slots__ = ("region", "mig_left")

    def __init__(self, row: int, job: RJob, dtype, region: int):
        super().__init__(row, job, dtype)
        self.region = region
        self.mig_left = 0


def _fcfs(live) -> list:
    """Forced jobs first, then arrival and job id; a migrating job is not
    schedulable."""
    return sorted((a for a in live if a.mig_left <= 0),
                  key=lambda a: (not a.forced, a.job.arrival, a.job.job_id))


class _GeoPolicy:
    """What the three policies share: the cluster, the CI views and the
    migration rule's arithmetic."""

    saving_margin = 0.25
    max_moves = 1

    def start(self, traces, caps, migration, dtype):
        self.traces, self.caps = traces, caps
        self.mig, self.dtype = migration, dtype
        self.placed: dict[int, int] = {}
        self.moves: dict[int, int] = {}

    def ci_now(self, t):
        return self.traces[:, min(t, self.traces.shape[1] - 1)]

    def done(self, row: int) -> None:
        self.placed.pop(row, None)
        self.moves.pop(row, None)

    def _target(self, a, r: int, h: int, stay_ci, move_ci, ci_now):
        """Region to move ``a`` to, iff moving beats staying by the
        margin: each region's CI over the remaining run (``move_ci``),
        times the run's energy, plus the transfer energy at its current
        CI, against staying at ``stay_ci``."""
        d = self.dtype
        power = a.job.power if a.job.power > 0 else POWER
        e_run = d(a.job.k_min) * d(power) * d(SLOT_H) * d(h)
        stay = stay_ci * e_run
        e_mig = self.mig.energy_kwh(a.job, d)
        move = move_ci * e_run + np.array([e_mig * c for c in ci_now],
                                          dtype=d)
        move[r] = np.inf
        best = int(np.argmin(move))
        if move[best] < stay * d(1.0 - self.saving_margin):
            return best
        return None

    def _may_move(self, a) -> int | None:
        """The migration's slots, or None where the job has moved its
        allowance or has too little slack or work left to absorb them."""
        if self.moves.get(a.row, 0) >= self.max_moves:
            return None
        ms = self.mig.slots(a.job)
        if a.slack_left <= ms + 1 or a.remaining <= ms:
            return None
        return ms

    def _moved(self, a, dest: int, k: int, alloc: dict) -> None:
        alloc[a.row] = (dest, k)
        self.placed[a.row] = dest
        self.moves[a.row] = self.moves.get(a.row, 0) + 1

    @staticmethod
    def _first_free(order, used, caps, k) -> int | None:
        return next((int(r) for r in order if used[r] + k <= caps[r]), None)


class GeoStatic(_GeoPolicy):
    """Every job pinned to its arrival region, FCFS at k_min."""

    name = "geo-static"

    def decide(self, t, live):
        used = np.zeros(len(self.caps), dtype=np.int64)
        alloc = {}
        for a in _fcfs(live):
            r, k = a.region, a.job.k_min
            if used[r] + k <= self.caps[r]:
                alloc[a.row] = (r, k)
                used[r] += k
        return alloc


class GeoGreedy(_GeoPolicy):
    """Place each job at admission in the region of lowest CI now with
    room; move a started job once when the CI now, over its remaining
    hours, pays for the move by the margin."""

    name = "geo-greedy"

    def decide(self, t, live):
        ci_now = self.ci_now(t)
        order = np.argsort(ci_now, kind="stable")
        used = np.zeros(len(self.caps), dtype=np.int64)
        alloc = {}
        for a in _fcfs(live):
            k = a.job.k_min
            if a.row not in self.placed:
                if a.started:
                    self.placed[a.row] = a.region
                else:
                    r = self._first_free(order, used, self.caps, k)
                    if r is None:
                        continue
                    self.placed[a.row] = r
            r = self.placed[a.row]
            if a.started:
                ms = self._may_move(a)
                if ms is not None:
                    h = int(max(1, np.ceil(a.remaining)))
                    dest = self._target(a, r, h, ci_now[r], ci_now, ci_now)
                    if dest is not None:
                        self._moved(a, dest, k, alloc)
                        continue
            if used[r] + k <= self.caps[r]:
                alloc[a.row] = (r, k)
                used[r] += k
        return alloc


class GeoFlex(_GeoPolicy):
    """Place each job in the region of lowest mean forecast over its run;
    run it only in slots at or below its region's 40th percentile of the
    next day (or once forced); move a started job once when another
    region's forecast over its remaining run, past the migration's
    slots, pays for the move by the margin."""

    name = "geo-flex"
    percentile = 40.0

    def decide(self, t, live):
        fc = np.stack([truth_slice(tr, t, LOOKAHEAD) for tr in self.traces])
        ci_now = self.ci_now(t)
        thresh = np.percentile(fc, self.percentile, axis=1)
        used = np.zeros(len(self.caps), dtype=np.int64)
        alloc = {}
        for a in _fcfs(live):
            k = a.job.k_min
            if not a.started:
                if a.row not in self.placed:
                    h = int(min(LOOKAHEAD, max(1, np.ceil(a.remaining))))
                    order = np.argsort(fc[:, :h].mean(axis=1), kind="stable")
                    r = self._first_free(order, used, self.caps, k)
                    if r is None:
                        continue
                    self.placed[a.row] = r
                r = self.placed[a.row]
            else:
                r = a.region
                ms = self._may_move(a)
                if ms is not None:
                    h = int(min(LOOKAHEAD - ms, max(1, np.ceil(a.remaining))))
                    if h >= 1:
                        dest = self._target(
                            a, r, h, fc[r, :h].mean(),
                            fc[:, ms:ms + h].mean(axis=1), ci_now)
                        if dest is not None:
                            self._moved(a, dest, k, alloc)
                            continue
            if a.forced or ci_now[r] <= thresh[r] + _EPS:
                if used[r] + k <= self.caps[r]:
                    alloc[a.row] = (r, k)
                    used[r] += k
        return alloc


POLICIES = {p.name: p for p in (GeoStatic, GeoGreedy, GeoFlex)}


def simulate_geo(jobs: list[RJob], traces, capacities: tuple[int, ...],
                 regions: tuple[str, ...], policy: _GeoPolicy, t0: int,
                 horizon: int = WEEK, migration: Migration = Migration(),
                 dtype=np.float64) -> dict:
    """The geo slot loop over ``jobs`` (the evaluated weeks)."""
    jobs = sorted(jobs, key=lambda j: (j.arrival, j.job_id))
    n, n_reg = len(jobs), len(capacities)
    tr = np.stack([np.asarray(x, dtype=dtype) for x in traces])
    caps = np.asarray(capacities, dtype=np.int64)
    policy.start(tr, caps, migration, dtype)
    completion = [-1] * n
    wait = [0] * n
    viol = [False] * n
    final_region = [-1] * n
    reg_e = np.zeros(n_reg, dtype=dtype)
    reg_c = np.zeros(n_reg, dtype=dtype)
    tot_e, tot_c, mig_c = dtype(0.0), dtype(0.0), dtype(0.0)
    migrations = 0
    live: list[_GeoRun] = []
    slots = []
    nxt = 0
    t = t0
    while t < t0 + horizon + MAX_OVERRUN:
        while nxt < n and jobs[nxt].arrival <= t:
            live.append(_GeoRun(nxt, jobs[nxt], dtype, nxt % n_reg))
            nxt += 1
        if not live and nxt == n and t >= t0 + horizon:
            break
        alloc = policy.decide(t, live)
        run: dict[int, tuple[int, int]] = {}
        moving = []
        for a in live:
            entry = alloc.get(a.row)
            if entry is None or a.mig_left > 0:
                continue
            r, k = entry
            if r != a.region:
                a.region = r
                if a.started:
                    a.mig_left = migration.slots(a.job)
                    moving.append(a)
                    continue
            run[a.row] = (r, k)
        per_region = np.zeros(n_reg, dtype=np.int64)
        for r, k in run.values():
            per_region[r] += k
        if (per_region > caps).any():
            raise AssertionError(f"reference policy {policy.name} exceeded "
                                 f"a region's capacity at slot {t}")
        civ = tr[:, min(t, tr.shape[1] - 1)]
        energy_r = np.zeros(n_reg, dtype=dtype)
        for a in live:
            if a.row in run:
                r, k = run[a.row]
                thr = _thr(a.job, k, dtype)
                frac = min(dtype(1.0), a.remaining / max(thr, dtype(_EPS)))
                energy_r[r] += _slot_energy(a.job, k, frac, dtype)
        for a in moving:
            e = migration.energy_kwh(a.job, dtype)
            energy_r[a.region] += e
            mig_c += e * civ[a.region]
        migrations += len(moving)
        energy, carbon = dtype(0.0), dtype(0.0)
        for r in range(n_reg):
            c = energy_r[r] * civ[r]
            energy += energy_r[r]
            carbon += c
            reg_e[r] += energy_r[r]
            reg_c[r] += c
        tot_e += energy
        tot_c += carbon
        for a in live:
            if a.row in run:
                a.remaining -= _thr(a.job, run[a.row][1], dtype)
                a.started = True
            else:
                a.slack_left -= 1
                a.waited += 1
                if a.mig_left > 0:
                    a.mig_left -= 1
        still = []
        for a in live:
            if a.remaining <= _EPS:
                completion[a.row] = t
                wait[a.row] = a.waited
                viol[a.row] = t > a.job.deadline
                final_region[a.row] = a.region
                policy.done(a.row)
            else:
                still.append(a)
        live = still
        slots.append({"slot": t, "ci": float(np.mean(civ)),
                      "provisioned": int(caps.sum()),
                      "used": int(per_region.sum()),
                      "energy_kwh": float(energy), "carbon_g": float(carbon),
                      "running": len(run), "queued": len(live) - len(run)})
        t += 1
    return {"policy": policy.name, "carbon_g": float(tot_c),
            "energy_kwh": float(tot_e), "num_jobs": n,
            "wait_slots": [float(w) for w in wait], "violations": viol,
            "completion": completion, "final_region": final_region,
            "slots": slots, "regions": list(regions),
            "region_carbon_g": [float(x) for x in reg_c],
            "region_energy_kwh": [float(x) for x in reg_e],
            "migrations": migrations, "migration_carbon_g": float(mig_c)}
