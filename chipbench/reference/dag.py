"""Plain reference of a precedence-gated (DAG) workload, written from the
semantics the program documents (``core/dag.py``, the gating of the
``core/simulator.py`` slot loop, ``traces/workloads.py`` DAG trace) and
importing none of it.

The trace: whole DAGs arrive as diurnal Poisson arrivals calibrated so
the expected base-scale task demand is ``utilization * capacity``; each
is a chain, a map-reduce (source, fan-out, reducer) or a random layered
DAG, drawn uniformly; task lengths are log-normal hours clipped to 1..48;
each task draws a Table-3 profile and takes the queue its length falls
in.  Every task becomes one job arriving at its DAG's slot, with the job
ids of its predecessors as ``deps``.

The slot loop is ``sim.simulate_single``'s with gating: a task with an
unfinished predecessor is not admitted, not seen by the policy and burns
no slack.  When its last predecessor completes in slot ``t`` it is
released at ``t + 1``; its slack starts then, and its deadline counts
from the release (``release + ceil(length) + delay``).  The policies are
FCFS at ``k_min`` (``dag-fcfs``), FCFS in slots at or below the 40th
percentile of the next day's forecast (``dag-carbon``), and that rule
with every task on a longest path of its weakly connected component
exempt (``dag-cap``).  Every float of the loop is computed in ``dtype``
(see ``sim.py``).

Simplifications: one region and the perfect forecast; every task at
``k_min``, on a CPU cluster (power 1 kW a server); a task's predecessors
arrive with it (a DAG arrives whole), so no release precedes an arrival.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .sim import (_EPS, MAX_OVERRUN, CarbonAgnostic, WaitAwhile, _Run,
                  _slot_energy, _thr)
from .world import (_CLASS_SIGMA, QUEUES, TABLE3, TRACE_FAMILIES, WEEK,
                    RJob, amdahl_profile)


@dataclasses.dataclass(frozen=True)
class DagShape:
    """The DAG generator's knobs, named as the program's ``DagConfig``."""

    shapes: list[str]
    width: int                  # map-reduce fan-out and layer width, at most
    depth: int                  # chain tasks and layers, at most
    task_mu: float              # log-normal mu of task hours
    task_sigma: float
    max_parents: int            # layered: parents drawn per task, at most

    def expected_tasks(self) -> float:
        """Mean tasks a DAG under the shape mix (rate calibration)."""
        per = {"chain": (2 + self.depth) / 2,
               "mapreduce": (2 + self.width) / 2 + 2,
               "layered": ((2 + self.depth) / 2) * (1 + self.width) / 2}
        return float(np.mean([per[s] for s in self.shapes]))

    def mean_task(self) -> float:
        return max(1.0, float(np.exp(self.task_mu + self.task_sigma ** 2 / 2)))


def _layered_deps(sizes: list[int], rng: np.random.Generator,
                  max_parents: int) -> list[tuple[int, ...]]:
    """Each task of layer i > 0 draws 1..max_parents distinct parents
    uniformly from layer i - 1; indices within the DAG."""
    deps: list[tuple[int, ...]] = []
    prev: list[int] = []
    for size in sizes:
        cur = []
        for _ in range(size):
            d: tuple[int, ...] = ()
            if prev:
                n_par = int(rng.integers(1, min(max_parents, len(prev)) + 1))
                d = tuple(sorted(int(p) for p in rng.choice(
                    prev, size=n_par, replace=False)))
            cur.append(len(deps))
            deps.append(d)
        prev = cur
    return deps


def generate_dag_jobs(family: str, hours: int, capacity: int,
                      utilization: float, seed: int, shape: DagShape,
                      k_min: int = 1, k_max: int = 16) -> list[RJob]:
    """The seeded DAG trace, expanded to one job per task."""
    rng = np.random.default_rng(seed)
    _, _, diurnal = TRACE_FAMILIES[family]
    base_rate = utilization * capacity / (shape.expected_tasks()
                                          * shape.mean_task() * k_min)
    profiles = {c: amdahl_profile(k_min, k_max, s)
                for c, s in _CLASS_SIGMA.items()}

    def lengths(n: int) -> list[float]:
        raw = np.exp(rng.normal(shape.task_mu, shape.task_sigma, n))
        return [float(v) for v in np.clip(raw, 1.0, 48.0)]

    jobs: list[RJob] = []
    for t in range(hours):
        hod, dow = t % 24, (t // 24) % 7
        rate = base_rate * (1.0 + diurnal * np.sin(2 * np.pi * (hod - 10) / 24.0))
        if dow >= 5:
            rate *= 0.8
        for _ in range(rng.poisson(max(rate, 0.0))):
            kind = shape.shapes[rng.integers(len(shape.shapes))]
            if kind == "chain":
                d = int(rng.integers(2, shape.depth + 1))
                lens = lengths(d)
                deps = [(i - 1,) if i else () for i in range(d)]
            elif kind == "mapreduce":
                w = int(rng.integers(2, shape.width + 1))
                lens = lengths(w + 2)
                deps = [()] + [(0,)] * w + [tuple(range(1, w + 1))]
            else:
                d = int(rng.integers(2, shape.depth + 1))
                sizes = [int(rng.integers(1, shape.width + 1))
                         for _ in range(d)]
                lens = lengths(sum(sizes))
                deps = _layered_deps(sizes, rng, shape.max_parents)
            base = len(jobs)
            for length, dep in zip(lens, deps):
                _, comm_mb, cls, _ = TABLE3[rng.integers(len(TABLE3))]
                q = next(i for i, (_, mx) in enumerate(QUEUES) if length <= mx)
                jobs.append(RJob(job_id=len(jobs), arrival=t, length=length,
                                 queue=q, delay=QUEUES[q][0],
                                 profile=profiles[cls], k_min=k_min,
                                 power=1.0, comm_size=comm_mb / 1024.0,
                                 deps=tuple(base + p for p in dep)))
    return jobs


# --- criticality ---------------------------------------------------------------


def critical_tasks(jobs: list[RJob], dtype=np.float64) -> dict[int, bool]:
    """``{job_id: on a longest path}``: head (work up to and including a
    task) plus tail (from it to a sink) less its length reaches the
    longest path of its weakly connected component, to 1e-9."""
    row = {j.job_id: i for i, j in enumerate(jobs)}
    n = len(jobs)
    length = [dtype(j.length) for j in jobs]
    preds = [[row[d] for d in j.deps] for j in jobs]
    succs: list[list[int]] = [[] for _ in range(n)]
    for i, ps in enumerate(preds):
        for p in ps:
            succs[p].append(i)
    # deps point to earlier job ids: row order is topological
    head = [dtype(0.0)] * n
    tail = [dtype(0.0)] * n
    for i in range(n):
        head[i] = length[i] + max((head[p] for p in preds[i]), default=dtype(0.0))
    for i in reversed(range(n)):
        tail[i] = length[i] + max((tail[s] for s in succs[i]), default=dtype(0.0))
    comp = list(range(n))

    def find(x: int) -> int:
        while comp[x] != x:
            comp[x] = comp[comp[x]]
            x = comp[x]
        return x

    for i, ps in enumerate(preds):
        for p in ps:
            comp[find(p)] = find(i)
    longest: dict[int, float] = {}
    for i in range(n):
        r = find(i)
        longest[r] = max(longest.get(r, dtype(0.0)), head[i])
    return {jobs[i].job_id: bool(head[i] + tail[i] - length[i]
                                 >= longest[find(i)] - _EPS)
            for i in range(n)}


# --- policies --------------------------------------------------------------------


class DagFcfs(CarbonAgnostic):
    name = "dag-fcfs"


class DagCarbon(WaitAwhile):
    name = "dag-carbon"
    percentile = 40.0


class DagCap(DagCarbon):
    """dag-carbon with the tasks on a longest path never held back."""

    name = "dag-cap"

    def start(self, jobs, trace, forecast, t0, horizon, capacity, dtype):
        super().start(jobs, trace, forecast, t0, horizon, capacity, dtype)
        self.critical = critical_tasks(jobs, dtype)

    def eligible(self, t, a) -> bool:
        return super().eligible(t, a) or self.critical[a.job.job_id]


POLICIES = {p.name: p for p in (DagFcfs, DagCarbon, DagCap)}


# --- the gated slot loop -----------------------------------------------------------


def simulate_dag(jobs: list[RJob], trace: np.ndarray, forecast, capacity: int,
                 policy, t0: int, horizon: int = WEEK,
                 dtype=np.float64) -> dict:
    """The single-region slot loop over the tasks of the evaluated weeks,
    each gated until its predecessors complete."""
    jobs = sorted(jobs, key=lambda j: (j.arrival, j.job_id))
    n = len(jobs)
    row = {j.job_id: i for i, j in enumerate(jobs)}
    left = [len(j.deps) for j in jobs]
    succs: list[list[int]] = [[] for _ in range(n)]
    for i, j in enumerate(jobs):
        for d in j.deps:
            succs[row[d]].append(i)
    deadline = [j.deadline for j in jobs]
    trace_d = np.asarray(trace, dtype=dtype)
    policy.start(jobs, trace_d, forecast, t0, horizon, capacity, dtype)
    completion = [-1] * n
    wait = [0] * n
    viol = [False] * n
    active: list[_Run] = []
    released: list[int] = []        # rows whose last predecessor just ended
    gated = 0
    slots = []
    nxt = 0
    tot_e = dtype(0.0)
    tot_c = dtype(0.0)
    t = t0
    while t < t0 + horizon + MAX_OVERRUN:
        for r in released:
            active.append(_Run(r, jobs[r], dtype))
            deadline[r] = t + jobs[r].deadline - jobs[r].arrival
        gated -= len(released)
        released = []
        while nxt < n and jobs[nxt].arrival <= t:
            if left[nxt]:
                gated += 1
            else:
                active.append(_Run(nxt, jobs[nxt], dtype))
            nxt += 1
        active.sort(key=lambda a: a.row)
        if not active and nxt == n and not gated and t >= t0 + horizon:
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
                viol[a.row] = t > deadline[a.row]
                for c in succs[a.row]:
                    left[c] -= 1
                    if not left[c]:
                        released.append(c)
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
