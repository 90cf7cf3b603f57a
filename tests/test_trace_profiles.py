"""Per-call profile sharing in the trace generators.

``generate_trace`` and ``generate_dag_specs`` build each parametric profile
once per call and hand every job of that class the same read-only array.
The job lists must stay bit-equal to the per-job build (the digests below
were recorded from the per-job generator), nothing may outlive a call, and
``Scenario.materialize`` counts the jobs and distinct arrays it generated.
"""
import dataclasses
import hashlib

import numpy as np
import pytest

from repro.core.profiles import TABLE3_WORKLOADS, class_profile
from repro.experiment import Scenario
from repro.telemetry.profiler import PhaseProfiler
from repro.traces import workloads
from repro.traces.workloads import (DagConfig, TraceSpec, generate_dag_trace,
                                    generate_trace)

# A DAG task's ``arch`` is its task name, so the Table-3 workload is found
# by its communication size, which is unique in the table.
CLASS_OF = {w.comm_size_mb / 1024.0: w.scalability for w in TABLE3_WORKLOADS}
GENERATORS = {"flat": generate_trace,
              "dag": lambda spec: generate_dag_trace(spec, DagConfig())}


def _digest(jobs) -> str:
    """SHA-256 over every field of every job, the profile by its bytes."""
    h = hashlib.sha256()
    for j in jobs:
        for f in dataclasses.fields(j):
            v = getattr(j, f.name)
            if f.name == "profile":
                a = np.ascontiguousarray(v)
                h.update(f"{a.dtype.str}{a.shape}".encode())
                h.update(a.tobytes())
            else:
                h.update(f"{f.name}={v!r};".encode())
    return h.hexdigest()


def _class(job, spec: TraceSpec) -> str:
    return (CLASS_OF[job.comm_size] if spec.elasticity == "mix"
            else spec.elasticity)


@pytest.mark.parametrize("kind", sorted(GENERATORS))
@pytest.mark.parametrize("k_min,k_max", [(1, 16), (2, 8)])
@pytest.mark.parametrize("mode", ["cpu", "gpu"])
@pytest.mark.parametrize("elasticity",
                         ["mix", "high", "moderate", "low", "none"])
def test_profiles_equal_fresh_class_profile(kind, k_min, k_max, mode,
                                            elasticity):
    spec = TraceSpec(hours=72, seed=5, k_min=k_min, k_max=k_max,
                     elasticity=elasticity, mode=mode)
    jobs = GENERATORS[kind](spec)
    assert jobs
    for j in jobs:
        cls = _class(j, spec)
        fresh = (np.ones(1) if cls == "none"
                 else class_profile(cls, k_min, k_max))
        assert j.profile.dtype == fresh.dtype
        assert np.array_equal(j.profile, fresh)


# Recorded from the generator that built one profile per job.
TWO_WEEK_DIGESTS = {
    ("flat", 0): "6c0a0264ae204039a5ac759fba0161da8ba19eb8506362987fbbc07fd635abd5",
    ("flat", 1): "d99248bce910ce5965e903e04e81dcfcfe22a4f96be7ecb35c9f40e7fbc15f92",
    ("flat", 2): "b9eda79ea497a47332049875f93a1db56ca6612d320862c50af89b63c952782d",
    ("dag", 0): "872e541937172295a0797218d17e8aeafbc356fe4e3f41d56b92c70cf81f0270",
    ("dag", 1): "58d55dbd2f606af5707e8bdb5988517afeae85b163c873e2d5a3d9cafe46a257",
    ("dag", 2): "3071f1673259e9e6ebd7e4720e4c9a3db1691023d672fe33c088e09271a7d4d0",
}


@pytest.mark.parametrize("kind,seed", sorted(TWO_WEEK_DIGESTS))
def test_two_week_job_lists_bit_equal(kind, seed):
    jobs = GENERATORS[kind](TraceSpec(hours=24 * 14, seed=seed))
    assert _digest(jobs) == TWO_WEEK_DIGESTS[kind, seed]


@pytest.mark.parametrize("kind", sorted(GENERATORS))
def test_class_shares_one_read_only_array(kind):
    spec = TraceSpec(hours=24 * 7, seed=11)
    jobs = GENERATORS[kind](spec)
    ids: dict[str, set[int]] = {}
    for j in jobs:
        ids.setdefault(_class(j, spec), set()).add(id(j.profile))
    assert set(ids) == {"high", "moderate", "low"}
    assert all(len(s) == 1 for s in ids.values())
    with pytest.raises(ValueError):
        jobs[0].profile[0] = 0.5


@pytest.mark.parametrize("kind", sorted(GENERATORS))
def test_no_profile_outlives_its_call(kind):
    spec = TraceSpec(hours=48, seed=2)
    first, second = GENERATORS[kind](spec), GENERATORS[kind](spec)
    assert {id(j.profile) for j in first}.isdisjoint(
        id(j.profile) for j in second)


@pytest.mark.parametrize("extra,traces", [({}, 1), ({"eval_shift": 0.3}, 2),
                                         ({"dag": DagConfig()}, 1)])
def test_materialize_counts_jobs_and_profiles(extra, traces):
    sc = Scenario(capacity=20, learn_weeks=1, seed=4, **extra)
    prof = PhaseProfiler()
    mat = sc.materialize(profiler=prof)
    c = prof.counters
    assert set(c) == {"trace_jobs", "trace_profiles"}
    assert c["trace_profiles"] <= 3 * traces
    if traces == 1:
        assert c["trace_jobs"] == len(mat.jobs)
    else:    # the shifted trace replaces the evaluated weeks of the first
        assert c["trace_jobs"] > len(mat.jobs)


@pytest.fixture
def fake_dryrun(monkeypatch):
    """A ``profile_from_dryrun`` that needs no dry-run files, and an empty
    architecture-profile cache."""
    from repro.core import profiles

    monkeypatch.setattr(workloads, "_TPU_PROFILE_CACHE", {})
    monkeypatch.setattr(
        profiles, "profile_from_dryrun",
        lambda name, k_min=1, k_max=16: class_profile("moderate", k_min,
                                                      k_max))


def test_tpu_profile_cache_keys_on_scale(fake_dryrun):
    for k_max in (16, 8, 16):
        spec = TraceSpec(hours=48, seed=1, elasticity="tpu", k_max=k_max)
        jobs = generate_trace(spec)
        assert jobs
        assert {j.k_max for j in jobs} == {k_max}
        assert not {j.arch for j in jobs} & {w.name for w in TABLE3_WORKLOADS}
