"""Plain reference of the cluster simulation, written from the semantics
the program documents (``core/simulator.py`` slot loop, ``core/baselines``,
``core/mpc.py``, ``core/oracle.py``) and importing none
of it.

One slot at a time: admit the arrivals, let the policy decide which jobs
run at how many servers, charge energy (Eq. 1-3, the fractional last
slot charged for the work it needs), advance progress, burn slack of the
jobs left waiting, retire finished jobs.  Every float is computed in
``dtype``: float64 is the configuration's precision; float32 is the
control that the comparison must reject.

A result is a plain dict shaped like the program's ``SimResult.to_dict``
with per-job and per-slot detail.
"""
from __future__ import annotations

import numpy as np

from .world import WEEK, Forecast, RJob

MAX_OVERRUN = 24 * 21
PLAN_TAIL = 24 * 21
_EPS = 1e-9

SLOT_H = 1.0              # slot length, hours
POWER = 1.0               # kW per server when a job states none
ETA_NET = 0.1             # W/Gbps network energy


class _Run:
    """A job inside the cluster."""

    __slots__ = ("row", "job", "remaining", "slack_left", "waited",
                 "started")

    def __init__(self, row: int, job: RJob, dtype):
        self.row = row
        self.job = job
        self.remaining = dtype(job.length)
        self.slack_left = job.delay
        self.waited = 0
        self.started = False

    @property
    def forced(self) -> bool:
        return self.slack_left <= 0


def _thr(job: RJob, k: int, dtype) -> float:
    """Cumulative throughput at k servers (p(k_min) = 1)."""
    return dtype(np.sum(np.asarray(job.profile[:k - job.k_min + 1],
                                   dtype=dtype)))


def _slot_energy(job: RJob, k: int, frac, dtype):
    """kWh of ``job`` at ``k`` servers for ``frac`` of a slot."""
    power = dtype(job.power if job.power > 0 else POWER)
    e_compute = dtype(k) * power * dtype(SLOT_H) * frac
    ring = dtype(0.0) if k <= 1 else dtype(2.0) * dtype(k - 1) / dtype(k)
    gbits = dtype(job.comm_size) * dtype(8.0) * ring * dtype(k) * frac
    e_net = dtype(ETA_NET) * gbits / dtype(3600.0) / dtype(1000.0) \
        * dtype(SLOT_H)
    return e_compute + e_net


# --- single-region policies ----------------------------------------------------


class CarbonAgnostic:
    """FCFS at k_min, forced jobs first, skip what does not fit."""

    name = "carbon-agnostic"

    def start(self, jobs, trace, forecast, t0, horizon, capacity, dtype):
        self.capacity = capacity

    def eligible(self, t, a) -> bool:
        return True

    def decide(self, t, live):
        order = sorted(live, key=lambda a: (not a.forced, a.job.arrival,
                                            a.job.job_id))
        alloc, used = {}, 0
        for a in order:
            if not a.forced and not self.eligible(t, a):
                continue
            k = a.job.k_min
            if used + k > self.capacity:
                continue
            alloc[a.row] = k
            used += k
        return alloc


class WaitAwhile(CarbonAgnostic):
    """Run unforced jobs only in slots at or below the 30th percentile of
    the next day's forecast."""

    name = "wait-awhile"
    percentile = 30.0

    def start(self, jobs, trace, forecast, t0, horizon, capacity, dtype):
        super().start(jobs, trace, forecast, t0, horizon, capacity, dtype)
        self.trace, self.forecast = trace, forecast
        self._t, self._low = None, False

    def eligible(self, t, a) -> bool:
        if self._t != t:
            thresh = np.percentile(self.forecast.predict(self.trace, t, 24),
                                   self.percentile)
            now = self.trace[min(t, len(self.trace) - 1)]
            self._t, self._low = t, bool(now <= thresh + 1e-12)
        return self._low


class RecedingHorizon:
    """Run a live job now iff this slot is among the ``need`` cheapest of
    its feasible window under the forecast; ``need`` is the job's
    remaining work estimated from its queue's length history.  With
    ``scale_rho`` set (carbonflex-scale), in slots among the cheapest
    quarter of the horizon a job runs at the largest k whose marginal
    throughput clears ``scale_rho``."""

    horizon_h = 48
    percentile = 85.0
    prior_mean = 6.0
    history_cap = 512
    max_done = 64
    clean_frac = 0.25

    def __init__(self, history: list[RJob], n_queues: int,
                 scale_rho: float | None = None):
        self.name = "carbonflex-mpc" if scale_rho is None \
            else "carbonflex-scale"
        self.scale_rho = scale_rho
        hist = {q: [float(self.prior_mean)] for q in range(n_queues)}
        for j in history:
            h = hist[j.queue]
            h.append(float(j.length))
            if len(h) > self.history_cap:
                del h[0]
        self.hist = hist

    def _need_table(self, dtype):
        lut = np.ones((len(self.hist), self.max_done), dtype=np.int64)
        for q, h in self.hist.items():
            arr = np.asarray(h, dtype=dtype)
            for d in range(self.max_done):
                longer = arr[arr > d]
                if len(longer):
                    est = float(np.percentile(longer, self.percentile)) - d
                else:
                    est = max(float(arr.mean()) * 0.5, 1.0)
                lut[q, d] = max(int(np.ceil(est - 1e-9)), 1)
        return lut

    def start(self, jobs, trace, forecast, t0, horizon, capacity, dtype):
        h = self.horizon_h
        self.capacity, self.t0, self.dtype = capacity, t0, dtype
        self.need = self._need_table(dtype)
        span = horizon + PLAN_TAIL
        self.rank = np.zeros((span, h), dtype=np.int64)
        self.clean = np.zeros(span, dtype=bool)
        for s in range(span):
            w = np.asarray(forecast.extended(trace, t0 + s, h + 1),
                           dtype=dtype)
            cheaper = np.cumsum(w[1:] < w[0])
            self.rank[s, 1:] = cheaper[:h - 1]
            self.clean[s] = cheaper[h - 1] < int(np.ceil(self.clean_frac * h))
        self.k_up = {}
        for j in jobs:
            k = j.k_min
            if self.scale_rho is not None:
                for kk in range(j.k_min + 1, j.k_max + 1):
                    if j.profile[kk - j.k_min] >= self.scale_rho:
                        k = kk
                    else:
                        break
            self.k_up[j.job_id] = k

    def decide(self, t, live):
        s = min(max(t - self.t0, 0), len(self.rank) - 1)
        clean = self.scale_rho is not None and bool(self.clean[s])
        alloc, used = {}, 0
        for a in live:                       # forced first, row order
            if a.forced and used + a.job.k_min <= self.capacity:
                alloc[a.row] = a.job.k_min
                used += a.job.k_min
        for a in live:
            if a.forced:
                continue
            done = self.dtype(a.job.length) - a.remaining
            d = min(max(int(np.floor(done)), 0), self.max_done - 1)
            need = int(self.need[a.job.queue, d])
            w = min(max(a.slack_left + need, 1), self.horizon_h)
            if int(self.rank[s, w - 1]) >= need:
                continue
            k = self.k_up[a.job.job_id] if clean else a.job.k_min
            if used + k <= self.capacity:
                alloc[a.row] = k
                used += k
        return alloc


def simulate_single(jobs: list[RJob], trace: np.ndarray, forecast: Forecast,
                    capacity: int, policy, t0: int, horizon: int = WEEK,
                    dtype=np.float64) -> dict:
    """The single-region slot loop over ``jobs`` (the evaluated weeks)."""
    jobs = sorted(jobs, key=lambda j: (j.arrival, j.job_id))
    n = len(jobs)
    trace_d = np.asarray(trace, dtype=dtype)
    policy.start(jobs, trace_d, forecast, t0, horizon, capacity, dtype)
    completion = [-1] * n
    wait = [0] * n
    viol = [False] * n
    active: list[_Run] = []
    slots = []
    nxt = 0
    tot_e = dtype(0.0)
    tot_c = dtype(0.0)
    t = t0
    while t < t0 + horizon + MAX_OVERRUN:
        while nxt < n and jobs[nxt].arrival <= t:
            active.append(_Run(nxt, jobs[nxt], dtype))
            nxt += 1
        if not active and nxt == n and t >= t0 + horizon:
            break
        alloc = policy.decide(t, active)
        if sum(alloc.values()) > capacity:
            raise AssertionError(f"reference policy {policy.name} exceeded "
                                 f"capacity at slot {t}")
        civ = trace_d[min(t, len(trace_d) - 1)]
        energy = dtype(0.0)
        for a in active:
            k = alloc.get(a.row, 0)
            if k > 0:
                thr = _thr(a.job, k, dtype)
                frac = min(dtype(1.0), a.remaining / max(thr, dtype(_EPS)))
                energy += _slot_energy(a.job, k, frac, dtype)
        carbon = energy * civ
        tot_e += energy
        tot_c += carbon
        for a in active:
            k = alloc.get(a.row, 0)
            if k > 0:
                a.remaining -= _thr(a.job, k, dtype)
                a.started = True
            else:
                a.slack_left -= 1
                a.waited += 1
        still = []
        for a in active:
            if a.remaining <= _EPS:
                completion[a.row] = t
                wait[a.row] = a.waited
                viol[a.row] = t > a.job.deadline
            else:
                still.append(a)
        active = still
        slots.append({"slot": t, "ci": float(civ), "provisioned": capacity,
                      "used": int(sum(alloc.values())),
                      "energy_kwh": float(energy), "carbon_g": float(carbon),
                      "running": len(alloc),
                      "queued": len(active) - len(alloc)})
        t += 1
    return {"policy": policy.name, "carbon_g": float(tot_c),
            "energy_kwh": float(tot_e), "num_jobs": n,
            "wait_slots": [float(w) for w in wait], "violations": viol,
            "completion": completion, "slots": slots}


# --- learning: the offline oracle's marginal-capacity curve ------------------


def oracle_rho(jobs: list[RJob], ci: np.ndarray, capacity: int,
               horizon: int, max_extensions: int = 8,
               extension: int = 24) -> np.ndarray:
    """Algorithm 1 over one window and its per-slot rho: greedily take
    (job, slot, k) increments by marginal throughput per unit carbon
    (ties: earlier deadline, then job, k and slot order) within capacity
    until each job's work is done; extend the slack of unfinished jobs
    and retry while that can still admit new slots.  rho_t is the least
    marginal throughput among the jobs running in slot t, 1 when none."""
    n = len(jobs)
    delays = [j.delay for j in jobs]
    lengths = [j.length for j in jobs]
    for attempt in range(max_extensions + 1):
        dls = [j.arrival + int(np.ceil(j.length)) + d
               for j, d in zip(jobs, delays)]
        js, ks, ts, gains, scores, dlv = [], [], [], [], [], []
        for i, j in enumerate(jobs):
            lo, hi = max(j.arrival, 0), min(horizon, dls[i] + 1)
            if hi <= lo:
                continue
            slots_ = np.arange(lo, hi)
            for k in range(j.k_min, j.k_max + 1):
                g = float(j.profile[k - j.k_min])
                if g <= 0:
                    continue
                js.append(np.full(len(slots_), i))
                ks.append(np.full(len(slots_), k))
                ts.append(slots_)
                gains.append(np.full(len(slots_), g))
                scores.append(g / ci[slots_])
                dlv.append(np.full(len(slots_), dls[i]))
        if not js:
            return np.ones(horizon)
        js, ks, ts = (np.concatenate(x) for x in (js, ks, ts))
        gains, scores, dlv = (np.concatenate(x) for x in (gains, scores, dlv))
        order = np.lexsort((dlv, -scores))
        alloc = np.zeros((n, horizon), dtype=np.int64)
        used = [0] * horizon
        work = [0.0] * n
        for e in order.tolist():
            i, t, k = int(js[e]), int(ts[e]), int(ks[e])
            if work[i] >= lengths[i] - _EPS:
                continue
            base = k == jobs[i].k_min
            if (base and alloc[i, t] != 0) or (not base
                                               and alloc[i, t] != k - 1):
                continue
            add = jobs[i].k_min if base else 1
            if used[t] + add > capacity:
                continue
            alloc[i, t] = k
            used[t] += add
            work[i] += 1.0 if base else float(gains[e])
        unfinished = [i for i in range(n) if work[i] < lengths[i] - 1e-6]
        if not unfinished or attempt == max_extensions:
            break
        if not any(dls[i] + 1 < horizon for i in unfinished):
            break
        for i in unfinished:
            delays[i] += extension
    rho = np.ones(horizon)
    for t in range(horizon):
        vals = [float(jobs[i].profile[alloc[i, t] - jobs[i].k_min])
                for i in range(n) if alloc[i, t] > 0]
        if vals:
            rho[t] = min(vals)
    return rho


def learned_scale_rho(hist: list[RJob], trace: np.ndarray, capacity: int,
                      learn_weeks: int) -> tuple[float, int]:
    """The scale-up threshold carbonflex-scale learns: the median of the
    oracle's rho over every learning week that holds arrivals.  Returns
    it with the number of learned states (one per slot of those weeks)."""
    vals = []
    for w in range(learn_weeks):
        s0 = w * WEEK
        window = [RJob(j.job_id, j.arrival - s0, j.length, j.queue, j.delay,
                       j.profile, j.k_min, j.power, j.comm_size)
                  for j in hist if s0 <= j.arrival < s0 + WEEK]
        if window:
            vals.append(oracle_rho(window, trace[s0:s0 + WEEK], capacity,
                                   WEEK))
    if not vals:
        return 0.5, 0
    allv = np.concatenate(vals)
    return float(np.median(allv)), len(allv)
