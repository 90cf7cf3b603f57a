"""Packed job tables: ``PackedJobs`` and ``EntryBlocks.build`` compute the
profile-derived tables once per distinct ``(k_min, profile)`` and gather
them by row.  Every field must equal a plain per-job build (the loops
below, one ``Job`` call per job and scale), and the pack counters must
count cache misses only."""
import numpy as np
import pytest

from repro.core import CarbonService, ClusterConfig, baselines
from repro.core import simulator as sim
from repro.core.scan_engine import simulate_many_scan
from repro.core.scheduling import EntryBlocks
from repro.core.simulator import PackedJobs, SimCase
from repro.core.types import Job
from repro.telemetry import PhaseProfiler, Telemetry
from repro.traces import (DagConfig, TraceSpec, generate_dag_trace,
                          generate_trace)

WEEK = 24 * 7


def _per_job_build(jobs):
    """Every table field of a pack, built one job at a time."""
    n = len(jobs)
    kmax_g = max((j.k_max for j in jobs), default=0)
    thr = np.zeros((n, kmax_g + 1))
    for i, job in enumerate(jobs):
        for k in range(1, kmax_g + 1):
            thr[i, k] = job.throughput(k)
    ps, ks, off, cnt = [], [], [], []
    for job in jobs:
        pairs = [(job.marginal(k), k)
                 for k in range(job.k_min, job.k_max + 1)
                 if job.marginal(k) > 0]
        off.append(sum(cnt))
        cnt.append(len(pairs))
        ps += [p for p, _ in pairs]
        ks += [k for _, k in pairs]
    id2row = {j.job_id: i for i, j in enumerate(jobs)}
    pred0 = np.zeros(n, dtype=np.int64)
    succ = [[] for _ in range(n)]
    for i, job in enumerate(jobs):
        for d in job.deps:
            pred0[i] += 1
            succ[id2row[d]].append(i)
    arrival = np.array([j.arrival for j in jobs], dtype=np.int64)
    deadline = np.array([j.deadline for j in jobs], dtype=np.int64)
    return {
        "job_ids": np.array([j.job_id for j in jobs], dtype=np.int64),
        "arrival": arrival,
        "length": np.array([j.length for j in jobs], dtype=np.float64),
        "queue": np.array([j.queue for j in jobs], dtype=np.int64),
        "k_min": np.array([j.k_min for j in jobs], dtype=np.int64),
        "k_max": np.array([j.k_max for j in jobs], dtype=np.int64),
        "deadline": deadline,
        "elast": np.array([j.elasticity() for j in jobs], dtype=np.float64),
        "power": np.array([j.power for j in jobs], dtype=np.float64),
        "comm": np.array([j.comm_size for j in jobs], dtype=np.float64),
        "thr_tab": thr,
        "dl_span": deadline - arrival,
        "pred0": pred0,
        "succ_ptr": np.cumsum([0] + [len(s) for s in succ]),
        "succ_rows": np.array([r for s in succ for r in s], dtype=np.int64),
        "blocks.flat_p": np.array(ps, dtype=np.float64),
        "blocks.flat_k": np.array(ks, dtype=np.int64),
        "blocks.off": np.array(off, dtype=np.int64),
        "blocks.cnt": np.array(cnt, dtype=np.int64),
    }, id2row


def _job(jid, arrival, profile, k_min=1, length=3.5, deps=()):
    return Job(job_id=jid, arrival=arrival, length=length, queue=jid % 3,
               delay=4 + jid % 5, profile=profile, k_min=k_min,
               power=0.5 + 0.01 * jid, comm_size=0.1 * (jid % 4), deps=deps)


def _distinct(seed=3, n=200):
    rng = np.random.default_rng(seed)
    return [_job(i, int(rng.integers(0, 48)),
                 rng.uniform(-0.2, 1.0, int(rng.integers(1, 17))),
                 length=float(rng.uniform(0.5, 30.0)))
            for i in range(n)]


def _mixed_kmin(seed=5, n=120):
    """k_min 1-3 over a small pool of profiles of differing lengths, some
    repeated as copies, lists or float32, so that groups are shared, the
    same bytes at another k_min form another group, and the global k_max
    exceeds most jobs' (the ``k < k_min`` slices of ``throughput``)."""
    rng = np.random.default_rng(seed)
    pool = [np.array([1.0, 0.8, 0.5]), np.array([1.0]),
            np.array([1.0, 0.9, 0.7, 0.4, 0.2, 0.1, 0.0, -0.1]),
            np.array([1.0, 0.3], dtype=np.float32), [1.0, 0.6, 0.25, 0.1]]
    jobs = []
    for i in range(n):
        prof = pool[int(rng.integers(len(pool)))]
        prof = list(prof) if i % 7 == 0 else np.array(prof, copy=True)
        jobs.append(_job(i, int(rng.integers(0, 24)), prof,
                         k_min=int(rng.integers(1, 4)),
                         length=float(rng.uniform(1.0, 12.0))))
    return jobs


def _sorted(jobs):
    return sorted(jobs, key=lambda j: (j.arrival, j.job_id))


CASES = {
    "paper-mix-week": lambda: generate_trace(TraceSpec(seed=7)),
    "distinct-profiles": _distinct,
    "mixed-k-min": _mixed_kmin,
    "rigid": lambda: generate_trace(TraceSpec(capacity=20, seed=11,
                                              elasticity="none")),
    "dag": lambda: generate_dag_trace(TraceSpec(capacity=20, seed=4),
                                      DagConfig()),
    "one-job": lambda: [_job(0, 2, np.array([1.0, 0.5]), k_min=2)],
    "empty": lambda: [],
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_pack_tables_equal_per_job_build(case):
    jobs = _sorted(CASES[case]())
    want, id2row = _per_job_build(jobs)
    packed = PackedJobs(jobs)
    standalone = EntryBlocks.build(jobs)
    for name, ref in want.items():
        obj, attr = ((packed.blocks, name[7:]) if name.startswith("blocks.")
                     else (packed, name))
        got = getattr(obj, attr)
        assert got.dtype == ref.dtype and got.shape == ref.shape, name
        assert np.array_equal(got, ref), name
        if name.startswith("blocks."):
            alone = getattr(standalone, attr)
            assert alone.dtype == ref.dtype and np.array_equal(alone, ref)
    assert packed.id2row == id2row
    assert packed.n == len(jobs)
    assert packed.has_deps == any(j.deps for j in jobs)
    profiles = {(j.k_min, np.asarray(j.profile).dtype.str,
                 np.asarray(j.profile).tobytes()) for j in jobs}
    assert packed.n_profiles == len(profiles)
    if case == "paper-mix-week":
        assert packed.n_profiles == 3


def test_cyclic_dag_still_rejected():
    jobs = [_job(0, 0, np.ones(2), deps=(1,)), _job(1, 0, np.ones(2), deps=(0,)),
            _job(2, 1, np.ones(2))]
    with pytest.raises(ValueError, match="dependency cycle"):
        PackedJobs(jobs)


def _cases(jobs, telemetry):
    cluster = ClusterConfig.default(20)
    ci = CarbonService.synthetic("texas", WEEK * 2 + 24 * 30, seed=1)
    return [SimCase(jobs=jobs, ci=ci, cluster=cluster, policy=mk(),
                    horizon=WEEK, engine="scan", telemetry=telemetry)
            for mk in (baselines.CarbonAgnosticPolicy,
                       baselines.WaitAwhilePolicy)]


def test_pack_counters_count_builds_not_hits(monkeypatch):
    monkeypatch.setattr(sim, "_PACK_CACHE", {})
    jobs = generate_trace(TraceSpec(capacity=20, seed=9))
    prof = PhaseProfiler()
    simulate_many_scan(_cases(jobs, Telemetry(profiler=prof)))
    assert prof.spans["pack"]["calls"] == 2
    assert prof.counters["pack_builds"] == 1
    assert prof.counters["pack_jobs"] == len(jobs)
    assert prof.counters["pack_profile_tables"] == 3

    # a second request over the same list hits the cache: nothing counted
    again = PhaseProfiler()
    simulate_many_scan(_cases(jobs, Telemetry(profiler=again)))
    assert not any(k.startswith("pack_") for k in again.counters)


def test_pack_counters_silent_without_profiler(monkeypatch):
    monkeypatch.setattr(sim, "_PACK_CACHE", {})
    counted = []
    monkeypatch.setattr(PhaseProfiler, "count",
                        lambda self, name, n=1: counted.append(name))
    jobs = generate_trace(TraceSpec(capacity=20, seed=9))
    simulate_many_scan(_cases(jobs, None))
    assert counted == []
