"""The benchmark's own copy of the world generators.

Copied from the program (``traces/workloads.py::generate_trace``,
``core/profiles.py`` parametric profiles and Table 3, ``core/types.py``
default queues, ``core/carbon.py::synthesize_trace`` and the
``core/forecast.py`` perfect and noisy forecast models; the DAG trace
generator is ``dag.py``'s) so that the yardstick keeps the arithmetic it
was proved with when the program changes.  A world here is plain data: a
list of :class:`RJob` and per-region hourly carbon-intensity arrays.
"""
from __future__ import annotations

import dataclasses
import zlib

import numpy as np

WEEK = 24 * 7
CI_MARGIN_HOURS = 24 * 30

# (mean g CO2/kWh, daily CoV) per region (core/carbon.py REGIONS)
REGIONS: dict[str, tuple[float, float]] = {
    "south-australia": (250.0, 0.45),
    "california": (230.0, 0.28),
    "texas": (400.0, 0.20),
    "germany": (380.0, 0.30),
    "netherlands": (350.0, 0.22),
    "washington": (100.0, 0.20),
    "ontario": (60.0, 0.12),
    "sweden": (30.0, 0.10),
    "virginia": (350.0, 0.05),
    "poland": (650.0, 0.07),
}

# (log-normal mu of hours, sigma, diurnal amplitude)
TRACE_FAMILIES: dict[str, tuple[float, float, float]] = {
    "azure": (1.6, 0.9, 0.35),
    "alibaba": (0.8, 0.8, 0.45),
    "surf": (1.2, 1.1, 0.25),
}

_CLASS_SIGMA = {"high": 0.05, "moderate": 0.35, "low": 0.9}

# Table 3: (name, comm size MB, scalability class, GPU power kW)
TABLE3 = (
    ("nbody-100k", 5.3, "high", 1.00),
    ("nbody-50k", 0.53, "high", 1.00),
    ("nbody-2k", 0.16, "moderate", 0.85),
    ("jacobi-10k", 0.1, "moderate", 0.85),
    ("jacobi-1k", 51.2, "low", 0.70),
    ("lammps", 28.6, "low", 0.70),
    ("gromacs", 7.16, "low", 0.70),
    ("vgg16", 233.1, "low", 0.70),
    ("resnet18", 44.7, "low", 0.72),
    ("resnet50", 97.8, "moderate", 0.85),
    ("efficientnetv2-s", 170.5, "high", 1.00),
    ("effnet-s", 82.7, "high", 1.00),
    ("vit-b32", 336.6, "moderate", 0.85),
)


@dataclasses.dataclass(frozen=True)
class RJob:
    """One batch job as the reference sees it."""

    job_id: int
    arrival: int
    length: float
    queue: int
    delay: int
    profile: np.ndarray          # marginal throughput of k_min, k_min+1, ...
    k_min: int
    power: float
    comm_size: float
    deps: tuple[int, ...] = ()   # job ids of the predecessors (a DAG task)

    @property
    def k_max(self) -> int:
        return self.k_min + len(self.profile) - 1

    @property
    def deadline(self) -> int:
        return int(self.arrival + int(np.ceil(self.length)) + self.delay)


# (slack slots, max length) per queue: short, medium, long
QUEUES = ((6, 2.0), (24, 12.0), (48, np.inf))


def amdahl_profile(k_min: int, k_max: int, sigma: float) -> np.ndarray:
    ks = np.arange(k_min - 1, k_max + 1, dtype=np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        t = np.where(ks > 0, ks / (1.0 + sigma * (ks - 1.0)), 0.0)
    marg = np.diff(t)
    p = np.maximum(marg / marg[0], 0.0)
    return np.minimum.accumulate(p)


def mean_length(family: str) -> float:
    mu, sigma, _ = TRACE_FAMILIES[family]
    return max(1.0, float(np.exp(mu + sigma ** 2 / 2)))


def generate_jobs(family: str, hours: int, capacity: int, utilization: float,
                  seed: int, k_min: int = 1, k_max: int = 16) -> list[RJob]:
    """Seeded diurnal Poisson arrivals of log-normal-length elastic jobs
    with Table-3 profiles (the CPU-cluster, ``elasticity="mix"`` trace)."""
    rng = np.random.default_rng(seed)
    mu, sigma, diurnal = TRACE_FAMILIES[family]
    base_rate = utilization * capacity / (mean_length(family) * k_min)
    jobs: list[RJob] = []
    for t in range(hours):
        hod, dow = t % 24, (t // 24) % 7
        rate = base_rate * (1.0 + diurnal * np.sin(2 * np.pi * (hod - 10) / 24.0))
        if dow >= 5:
            rate *= 0.8
        for _ in range(rng.poisson(max(rate, 0.0))):
            length = float(np.clip(float(np.exp(rng.normal(mu, sigma))),
                                   1.0, 24 * 4))
            q = next(i for i, (_, mx) in enumerate(QUEUES) if length <= mx)
            _, comm_mb, cls, _ = TABLE3[rng.integers(len(TABLE3))]
            jobs.append(RJob(job_id=len(jobs), arrival=t, length=length,
                             queue=q, delay=QUEUES[q][0],
                             profile=amdahl_profile(k_min, k_max,
                                                    _CLASS_SIGMA[cls]),
                             k_min=k_min, power=1.0,
                             comm_size=comm_mb / 1024.0))
    return jobs


def synthesize_ci(region: str, hours: int, seed: int) -> np.ndarray:
    """Seeded hourly CI trace: daily and half-daily harmonics, a weekly
    component and AR(1) noise, scaled to the region's mean and CoV."""
    mean, cov = REGIONS[region]
    rng = np.random.default_rng(np.random.SeedSequence(
        [seed, zlib.crc32(region.encode()) & 0x7FFFFFFF]))
    t = np.arange(0, hours, dtype=np.float64)
    phase = rng.uniform(0, 2 * np.pi)
    daily = np.sin(2 * np.pi * (t - 14.0) / 24.0 + 0.0)
    half = 0.35 * np.sin(4 * np.pi * t / 24.0 + phase)
    weekly = 0.15 * np.sin(2 * np.pi * t / (24.0 * 7.0) + phase / 2)
    eps = rng.normal(0.0, 1.0, hours)
    ar = np.empty(hours)
    acc = 0.0
    for i in range(hours):
        acc = 0.85 * acc + eps[i]
        ar[i] = acc
    ar *= 0.25 / max(ar.std(), 1e-9)
    shape = daily + half + weekly + ar
    shape /= max(shape.std(), 1e-9)
    return np.clip(mean * (1.0 + cov * shape), 10.0, None)


# --- forecasts ---------------------------------------------------------------


def truth_slice(trace: np.ndarray, t: int, horizon: int) -> np.ndarray:
    end = min(t + horizon, len(trace))
    out = trace[t:end]
    if len(out) < horizon:
        out = np.concatenate(
            [out, np.full(horizon - len(out), out[-1] if len(out) else 0.0)])
    return out


@dataclasses.dataclass(frozen=True)
class Forecast:
    """Perfect (``sigma is None``) or seeded AR(1) noisy day-ahead
    forecast, deterministic per (seed, trace, query slot)."""

    sigma: float | None = None
    seed: int = 0

    PHI = 0.9             # AR(1) coefficient of the error path
    FLOOR = 1.0           # g/kWh floor of a noisy forecast
    DAY = 24              # day-ahead horizon, slots

    def predict(self, trace: np.ndarray, t: int, horizon: int) -> np.ndarray:
        truth = truth_slice(trace, t, horizon)
        if self.sigma is None:
            return truth
        salt = 0
        if len(trace):
            bits = int(np.float64(trace[0]).view(np.uint64))
            salt = (bits ^ (len(trace) << 1)) & 0xFFFFFFFFFFFFFFFF
        rng = np.random.default_rng(np.random.SeedSequence(
            [1, self.seed, max(int(t), 0), salt]))
        z = rng.normal(0.0, 1.0, horizon)
        c = self.sigma * np.sqrt(max(1.0 - self.PHI * self.PHI, 0.0))
        err = np.zeros(horizon)
        acc = 0.0
        for i in range(1, horizon):
            acc = self.PHI * acc + c * z[i]
            err[i] = acc
        return np.where(truth > 0.0,
                        np.clip(truth * (1.0 + err), self.FLOOR, None), truth)

    def extended(self, trace: np.ndarray, t: int, horizon: int) -> np.ndarray:
        """Past the day-ahead horizon the day is tiled (persistence)."""
        d = self.predict(trace, t, self.DAY)
        if horizon <= len(d):
            return d[:horizon]
        return np.tile(d, int(np.ceil(horizon / len(d))))[:horizon]


# --- worlds --------------------------------------------------------------------


@dataclasses.dataclass
class World:
    """One materialised deployment week: the jobs, the CI traces and the
    split between learning and evaluated weeks."""

    regions: tuple[str, ...]
    traces: tuple[np.ndarray, ...]     # one CI trace per region
    jobs: list[RJob]
    hist: list[RJob]
    eval_jobs: list[RJob]
    t0: int
    capacities: tuple[int, ...]        # per region (one entry single-region)


def _jobs(family: str, hours: int, capacity: int, utilization: float,
          seed: int, dag: dict | None) -> list[RJob]:
    """Independent jobs, or the tasks of a DAG trace when ``dag`` holds
    the DAG generator's knobs (``dag.DagShape`` fields)."""
    if dag is None:
        return generate_jobs(family, hours, capacity, utilization, seed)
    from .dag import DagShape, generate_dag_jobs

    return generate_dag_jobs(family, hours, capacity, utilization, seed,
                             DagShape(**dag))


def build_world(*, regions: tuple[str, ...], family: str, capacity: int,
                utilization: float, learn_weeks: int, eval_weeks: int,
                seed: int, dag: dict | None = None) -> World:
    """The world a ``Scenario`` with these fields materialises: CI from
    ``seed``, jobs (or DAG tasks) from ``seed + 1``, capacity split evenly
    over the regions with the remainder to the first."""
    hours = WEEK * (learn_weeks + eval_weeks)
    t0 = WEEK * learn_weeks
    traces = tuple(synthesize_ci(r, hours + CI_MARGIN_HOURS, seed)
                   for r in regions)
    jobs = _jobs(family, hours, capacity, utilization, seed + 1, dag)
    base, rem = divmod(int(capacity), len(regions))
    caps = tuple(base + (1 if i < rem else 0) for i in range(len(regions)))
    return World(regions=tuple(regions), traces=traces, jobs=jobs,
                 hist=[j for j in jobs if j.arrival < t0],
                 eval_jobs=[j for j in jobs if t0 <= j.arrival < hours],
                 t0=t0, capacities=caps)


def eval_job_count(*, family: str, capacity: int, utilization: float,
                   learn_weeks: int, eval_weeks: int, seed: int,
                   dag: dict | None = None) -> int:
    """Jobs (tasks, in a DAG trace) arriving in the evaluated weeks of the
    world of ``seed``."""
    hours = WEEK * (learn_weeks + eval_weeks)
    jobs = _jobs(family, hours, capacity, utilization, seed + 1, dag)
    t0 = WEEK * learn_weeks
    return sum(1 for j in jobs if t0 <= j.arrival < hours)
