"""Telemetry layer (ISSUE 9): per-slot decision traces, carbon
attribution, and phase profiling.

Pins the three tentpole contracts:

- **cross-engine stream equality** — scalar, vector and scan produce the
  identical event list for the same case (the scan engine decodes its
  events host-side from the packed device grids, so this is a real
  equivalence, not a shared code path);
- **observation-only recording** — attaching a recorder changes no
  result float (and ``telemetry=None`` costs the off paths nothing; the
  golden fixtures pin byte-identity separately);
- **attribution additivity** — the cause decomposition sums float-exact
  (``==``, no tolerance) to the measured savings delta, via a hypothesis
  property over synthetic aggregates plus fixed twins on real sweeps.
"""
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import CarbonDataOutage, baselines, simulate
from repro.core.faults import (CorrelatedFaults, IidFaults, PreemptionFaults,
                               SlotDisturbance)
from repro.experiment import Scenario, Sweep, prepare_context
from repro.experiment.registry import make_policy
from repro.telemetry import (CAUSES, MemoryRecorder, PhaseProfiler,
                             SlotEventTracker, Telemetry, TraceEvent,
                             attribute, emit_fault_events, explain)

WEEK = 24 * 7
ENGINES = ("scalar", "vector", "scan")


def tiny(seed=101, **kw):
    kw.setdefault("capacity", 8)
    kw.setdefault("learn_weeks", 1)
    kw.setdefault("family", "alibaba")
    return Scenario(seed=seed, **kw).materialize()


def run_with_recorder(mat, policy, engine, **kw):
    tel = Telemetry(recorder=MemoryRecorder())
    res = simulate(mat.eval_jobs, mat.ci, mat.cluster, policy, t0=mat.t0,
                   horizon=WEEK, engine=engine, telemetry=tel, **kw)
    return tel.recorder.events, res


# --- recorder / tracker units ----------------------------------------------


def test_emit_is_noop_without_recorder():
    tel = Telemetry()
    tel.emit(0, "admit", job=1)          # must not raise, records nothing
    assert tel.recorder is None


def test_for_run_stamps_label_on_shared_recorder():
    rec = MemoryRecorder()
    tel = Telemetry(recorder=rec)
    tel.for_run("a").emit(0, "admit", job=1)
    tel.for_run("b").emit(1, "admit", job=2)
    assert [e.run for e in rec.events] == ["a", "b"]
    assert len(rec.for_run("a")) == 1
    assert rec.counts(run="b") == {"admit": 1}


def test_memory_recorder_queries_and_clear():
    rec = MemoryRecorder()
    tel = Telemetry(recorder=rec)
    tel.emit(0, "admit", job=1)
    tel.emit(1, "suspend", job=1)
    tel.emit(2, "resume", job=1, value=2.0)
    assert rec.counts() == {"admit": 1, "suspend": 1, "resume": 1}
    assert [e.t for e in rec.by_kind("suspend")] == [1]
    assert len(rec) == 3
    rec.clear()
    assert len(rec) == 0


def test_trace_event_shape():
    e = TraceEvent(t=3, kind="scale", job=7, value=4.0, detail="from=2")
    assert e.to_dict() == {"t": 3, "kind": "scale", "job": 7, "value": 4.0,
                           "detail": "from=2", "run": ""}


def test_tracker_derives_lifecycle_events():
    rec = MemoryRecorder()
    tr = SlotEventTracker(Telemetry(recorder=rec))
    tr.step(0, [1, 2], [2, 4])           # first starts: no events
    tr.step(1, [1, 2], [2, 8])           # job 2 scales 4 -> 8
    tr.step(2, [2], [8])                 # job 1 suspends
    tr.step(3, [1, 2], [2, 8])           # job 1 resumes
    tr.finish(2)
    tr.step(4, [1], [2])                 # job 2 finished: no suspend
    kinds = [(e.kind, e.job) for e in rec.events]
    assert kinds == [("scale", 2), ("suspend", 1), ("resume", 1)]
    assert rec.by_kind("scale")[0].value == 8.0
    assert rec.by_kind("scale")[0].detail == "from=4"


def test_tracker_steady_state_fast_path_changes_nothing():
    """The identical-stream shortcut must derive the same events as a
    tracker that never takes it (lists vs generators force both paths)."""
    streams = [([1, 2], [2, 4]), ([1, 2], [2, 4]), ([1, 2], [2, 4]),
               ([2], [4]), ([1, 2], [2, 4]), ([1, 2], [3, 4])]
    fast, slow = MemoryRecorder(), MemoryRecorder()
    trf = SlotEventTracker(Telemetry(recorder=fast))
    trs = SlotEventTracker(Telemetry(recorder=slow))
    for t, (ids, ks) in enumerate(streams):
        trf.step(t, ids, ks)                       # lists: fast path eligible
        trs.step(t, iter(ids), iter(ks))           # generators: full walk
    assert fast.events == slow.events


def test_fault_event_decoding():
    rec = MemoryRecorder()
    tel = Telemetry(recorder=rec)
    dist = SlotDisturbance(
        factors=np.array([1.0, 0.0, 0.5]),
        evicted=np.array([True, False, False]),
        lost=np.array([0.0, 3.0, 0.0]),
        extra_energy=np.array([0.0, 0.25, 0.0]))
    emit_fault_events(tel, 5, [10, 11, 12], dist, "preemption")
    kinds = [(e.kind, e.job, e.value) for e in rec.events]
    assert kinds == [("evict", 10, None), ("preempt", 11, 3.0),
                     ("restore", 11, 0.25), ("checkpoint", 12, 0.5)]


# --- cross-engine event-stream parity --------------------------------------


@pytest.mark.parametrize("mk", [baselines.CarbonAgnosticPolicy,
                                baselines.WaitAwhilePolicy])
def test_single_region_stream_parity(mk):
    mat = tiny()
    ref = None
    for eng in ENGINES:
        events, res = run_with_recorder(mat, mk(), eng)
        if ref is None:
            ref = (events, res.carbon_g)
            assert len(events) > 0
            assert all(e.kind == "admit" for e in events
                       if e.t == events[0].t)
        else:
            assert events == ref[0], eng
            assert res.carbon_g == ref[1], eng


def test_carbonflex_stream_parity_with_kb():
    mat = tiny()
    ctx = prepare_context(mat, ["carbonflex"])
    ref = None
    for eng in ENGINES:
        events, res = run_with_recorder(mat, make_policy("carbonflex", ctx),
                                        eng)
        if ref is None:
            ref = (events, res.carbon_g)
        else:
            assert (events, res.carbon_g) == ref, eng


@pytest.mark.parametrize("mkf,expected", [
    (lambda: IidFaults(straggler_rate=0.2, failure_rate=0.05, seed=3), ()),
    (lambda: PreemptionFaults(rate=0.2, seed=3),
     ("preempt", "restore", "checkpoint")),
    (lambda: CorrelatedFaults(n_domains=2, rate=0.1, seed=3), ("evict",)),
])
def test_fault_stream_parity(mkf, expected):
    mat = tiny()
    ref = None
    for eng in ENGINES:
        events, res = run_with_recorder(mat, baselines.WaitAwhilePolicy(),
                                        eng, faults=mkf())
        if ref is None:
            ref = (events, res.carbon_g)
            kinds = {e.kind for e in events}
            for kind in expected:
                assert kind in kinds, kind
        else:
            assert (events, res.carbon_g) == ref, eng


def test_dag_stream_parity():
    from repro.traces import DagConfig

    mat = tiny(dag=DagConfig(width=3, depth=3))
    ctx = prepare_context(mat, ["dag-cap"])
    for pol in ("dag-fcfs", "dag-cap"):
        ref = None
        for eng in ENGINES:
            events, res = run_with_recorder(mat, make_policy(pol, ctx), eng)
            if ref is None:
                ref = (events, res.carbon_g)
            else:
                assert (events, res.carbon_g) == ref, (pol, eng)


def test_geo_stream_parity_with_migrations():
    mat = tiny(regions=("california", "ontario"))
    ctx = prepare_context(mat, ["geo-flex"])
    ref = None
    for eng in ENGINES:
        tel = Telemetry(recorder=MemoryRecorder())
        res = simulate(mat.eval_jobs, mat.mci, mat.geo,
                       make_policy("geo-flex", ctx), t0=mat.t0,
                       horizon=WEEK, engine=eng, telemetry=tel)
        got = (tel.recorder.events, res.carbon_g)
        if ref is None:
            ref = got
            migs = [e for e in got[0] if e.kind == "migrate"]
            assert len(migs) == res.migrations > 0
            assert all(e.detail.startswith("from=") for e in migs)
        else:
            assert got == ref, eng


def test_outage_forecast_read_parity():
    mat = tiny(ci_outage=CarbonDataOutage(rate=0.1, mean_duration=6.0,
                                          stale_after=3, seed=5))
    ref = None
    for eng in ENGINES:
        events, res = run_with_recorder(mat, baselines.WaitAwhilePolicy(),
                                        eng)
        if ref is None:
            ref = (events, res.carbon_g)
            reads = [e for e in events if e.kind == "forecast-read"]
            assert reads and max(e.value for e in reads) > 0
        else:
            assert (events, res.carbon_g) == ref, eng


def test_serving_stream_parity_and_tier_switches():
    from repro.experiment import ServingConfig
    from repro.serving import simulate_serving

    mat = tiny(serving=ServingConfig(requests_per_day=2e5, servers=12),
               capacity=12)
    ctx = prepare_context(mat, ["serve-flex"])
    horizon = min(WEEK, mat.serving.demand.shape[0] - mat.t0)
    from repro.serving import ServeCase

    ref = None
    for eng in ("scalar", "vector"):
        tel = Telemetry(recorder=MemoryRecorder())
        case = ServeCase(demand=mat.serving.demand[mat.t0:mat.t0 + horizon],
                         rate=mat.serving.rate, ci=mat.ci,
                         config=mat.serving.config,
                         policy=make_policy("serve-flex", ctx), t0=mat.t0)
        res = simulate_serving(case, engine=eng, telemetry=tel)
        got = (tel.recorder.events, res.carbon_g)
        if ref is None:
            ref = got
            assert any(e.kind == "tier-switch" for e in got[0])
        else:
            assert got == ref, eng


# --- observation-only recording --------------------------------------------


@pytest.mark.parametrize("eng", ENGINES)
def test_recording_does_not_change_results(eng):
    mat = tiny()
    base = simulate(mat.eval_jobs, mat.ci, mat.cluster,
                    baselines.WaitAwhilePolicy(), t0=mat.t0, horizon=WEEK,
                    engine=eng)
    _, res = run_with_recorder(mat, baselines.WaitAwhilePolicy(), eng)
    assert res.to_dict() == base.to_dict()


# --- attribution -----------------------------------------------------------


def _stub(policy, carbon, energy, mig=0.0, restore=None, serving=False):
    class _R:
        pass

    r = _R()
    r.policy = policy
    r.carbon_g = carbon
    r.energy_kwh = energy
    r.regions = None
    r.slots = []
    r.migration_carbon_g = mig
    r.resilience = None
    r.serving = object() if serving else None
    if restore is not None:
        class _Res:
            restore_energy_kwh = restore

        r.resilience = _Res()
    return r


@settings(max_examples=200, deadline=None)
@given(bc=st.floats(1e-6, 1e9), rc=st.floats(0.0, 1e9),
       be=st.floats(0.0, 1e6), re_=st.floats(0.0, 1e6),
       bm=st.floats(0.0, 1e6), rm=st.floats(0.0, 1e6),
       br=st.floats(0.0, 1e3), rr=st.floats(0.0, 1e3),
       serving=st.booleans())
def test_attribution_additivity_property(bc, rc, be, re_, bm, rm, br, rr,
                                         serving):
    """sum(causes) == delta_g, float-exact, for arbitrary finite
    aggregates; delta_g equals the measured delta up to the documented
    lattice caveat (a few ulps, only under cancelling decompositions)."""
    res = _stub("p", rc, re_, mig=rm, restore=rr, serving=serving)
    base = _stub("b", bc, be, mig=bm, restore=br, serving=serving)
    att = attribute(res, base)
    att.check()                          # raises unless == holds
    total = 0.0
    for c in CAUSES:
        total += att.causes[c]
    assert total == att.delta_g
    scale = max(abs(att.causes[c]) for c in CAUSES) or 1.0
    assert abs(att.delta_g - (bc - rc)) <= 16 * math.ulp(scale)
    energy_axis = ("precision_tiering" if serving else "capacity_scaling")
    off_axis = ("capacity_scaling" if serving else "precision_tiering")
    assert att.causes[off_axis] == 0.0
    assert (att.causes[energy_axis] != 0.0) == (
        be != re_ and bc > 0 and be > 0)


def test_attribution_fixed_twin():
    """The additivity contract on one hand-checked example."""
    res = _stub("carbonflex", 700.0, 9.0)
    base = _stub("carbon-agnostic", 1000.0, 10.0)
    att = attribute(res, base)
    att.check()
    assert att.delta_g == 300.0
    assert att.causes["capacity_scaling"] == 100.0   # 1 kWh at 100 g/kWh
    assert att.causes["temporal_shifting"] == 200.0  # residual
    assert att.savings_pct == 30.0
    assert att.pp_of_baseline("capacity_scaling") == 10.0
    assert "carbonflex vs carbon-agnostic" in att.table()
    d = att.to_dict()
    assert set(d["causes"]) == set(CAUSES)


def test_sweep_attributions_additive_on_real_runs():
    sw = Sweep(base=Scenario(capacity=8, learn_weeks=1, family="alibaba",
                             seed=101),
               seeds=[11], policies=["carbon-agnostic", "wait-awhile"])
    res = sw.run()
    atts = res.attributions()            # check() runs inside
    assert len(atts) == 1
    att = atts[0]
    assert att.policy == "wait-awhile"
    assert att.baseline == "carbon-agnostic"
    row = [r for r in res.rows() if r["policy"] == "wait-awhile"][0]
    assert round(att.savings_pct, 2) == round(row["savings_pct"], 2)


def test_serving_sweep_attributions_use_tiering_axis():
    from repro.experiment import ServingConfig

    sw = Sweep(base=Scenario(serving=ServingConfig(requests_per_day=2e5,
                                                   servers=12),
                             learn_weeks=1, seed=101),
               seeds=[11], policies=["serve-static", "serve-flex"])
    atts = sw.run().attributions()
    assert [a.policy for a in atts] == ["serve-flex"]
    att = atts[0]
    assert att.causes["capacity_scaling"] == 0.0
    assert att.causes["precision_tiering"] != 0.0


# --- profiler / explain ----------------------------------------------------


def test_profiler_brackets_and_summary():
    prof = PhaseProfiler()
    with prof.phase("decide"):
        pass
    with prof.phase("decide"):
        pass
    with prof.phase("pack"):
        pass
    with prof.phase("decide/wait"):
        pass
    with prof.phase("execute", sync=np.zeros(3)):
        pass
    s = prof.summary()
    # phases first, each with the spans inside it, then the spans
    # between phases
    assert list(s["timers"]) == ["decide", "decide/wait", "execute", "pack"]
    assert s["timers"]["decide"]["calls"] == 2
    tops = [d for n, d in s["timers"].items() if "/" not in n]
    assert abs(sum(d["share"] for d in tops) - 1.0) < 1e-9
    assert s["counters"] == {}
    assert prof.total() > 0
    assert "decide" in prof.table() and "\n  decide/wait" in prof.table()


def test_phase_names_book_into_seconds_and_others_into_spans():
    from repro.telemetry import PHASES

    prof = PhaseProfiler()
    for name in PHASES + ("pack", "decide/wait", "learn/oracle"):
        with prof.phase(name):
            pass
    prof.add("rows", 0.5)
    assert set(prof.seconds) == set(PHASES)
    assert set(prof.spans) == {"pack", "decide/wait", "learn/oracle", "rows"}
    assert prof.spans["rows"] == {"seconds": 0.5, "calls": 1}
    assert prof.total() == sum(prof.seconds.values())


def test_count_adds_to_counters():
    prof = PhaseProfiler()
    prof.count("scan_slots", 168)
    prof.count("scan_slots", np.int64(24))
    prof.count("chunks")
    assert prof.counters == {"scan_slots": 192, "chunks": 1}
    assert prof.summary()["counters"] == prof.counters
    assert "scan_slots" in prof.table() and "192" in prof.table()


def test_span_without_profiler_is_a_shared_no_op():
    from repro.telemetry import span

    assert span(None, "decide") is span(None, "pack")
    with span(None, "decide/wait", sync=np.zeros(3)):
        pass


# --- spans and counters on the scan path -----------------------------------

SCAN_SPANS = {"pack", "policy_tables", "build", "provision/jobs",
              "learn/oracle", "decide/tables", "decide/upload",
              "decide/wait", "decide/fetch"}
SCAN_COUNTERS = {"scan_slots", "scan_slots_past_end", "h2d_bytes",
                 "d2h_bytes", "pack_builds", "pack_jobs",
                 "pack_profile_tables", "trace_jobs", "trace_profiles"}
SCAN_POLICIES = ["carbon-agnostic", "wait-awhile", "carbonflex-mpc"]


def _scan_run(telemetry=None):
    from repro.experiment import run

    res = run(Scenario(capacity=8, learn_weeks=1, family="alibaba", seed=101,
                       engine="scan"), SCAN_POLICIES, telemetry=telemetry)
    return [r.to_dict() for p in SCAN_POLICIES for r in res.weekly[p]]


def _scan_sweep(telemetry=None):
    sw = Sweep(base=Scenario(capacity=8, learn_weeks=1, family="alibaba",
                             engine="scan"),
               seeds=[1, 2], policies=SCAN_POLICIES, telemetry=telemetry)
    return sw.run().to_json()


def _geo_run(telemetry=None):
    from repro.experiment import run

    pols = ["geo-static", "geo-greedy", "geo-flex"]
    res = run(Scenario(regions=("south-australia", "california"),
                       capacity=10, learn_weeks=1, seed=3, family="alibaba",
                       engine="scan"), pols, telemetry=telemetry)
    return [r.to_dict() for p in pols for r in res.weekly[p]]


SCAN_CALLS = {"run": (_scan_run, SCAN_SPANS),
              "sweep": (_scan_sweep, SCAN_SPANS | {"rows"}),
              "geo": (_geo_run, SCAN_SPANS - {"learn/oracle"})}


@pytest.fixture(scope="module")
def scan_profiles():
    """Each scan-path call once detached and once with a profiler: (the
    results both ways, the profiler, the profiled call's wall seconds)."""
    import time

    out = {}
    for name, (call, _) in SCAN_CALLS.items():
        plain = call()
        prof = PhaseProfiler()
        t = time.perf_counter()
        profiled = call(Telemetry(profiler=prof))
        out[name] = (plain, profiled, prof, time.perf_counter() - t)
    return out


@pytest.mark.parametrize("call", sorted(SCAN_CALLS))
def test_scan_path_opens_every_span(scan_profiles, call):
    prof = scan_profiles[call][2]
    assert set(prof.seconds) == {"provision", "learn", "decide", "execute"}
    assert SCAN_CALLS[call][1] <= set(prof.spans)
    assert set(prof.summary()["timers"]) == set(prof.seconds) | set(prof.spans)
    table = prof.table()
    assert all(n.split("/")[-1] in table for n in SCAN_CALLS[call][1])


@pytest.mark.parametrize("call", sorted(SCAN_CALLS))
def test_scan_spans_fit_inside_wall_time_and_decide(scan_profiles, call):
    _, _, prof, wall = scan_profiles[call]
    tops = sum(d["seconds"] for n, d in prof.spans.items() if "/" not in n)
    assert prof.total() + tops <= wall
    parts = sum(d["seconds"] for n, d in prof.spans.items()
                if n.startswith("decide/"))
    assert 0 < parts <= prof.seconds["decide"]
    assert prof.spans["provision/jobs"]["seconds"] <= prof.seconds["provision"]


@pytest.mark.parametrize("call", sorted(SCAN_CALLS))
def test_scan_counters(scan_profiles, call):
    c = scan_profiles[call][2].counters
    assert set(c) == SCAN_COUNTERS
    assert c["scan_slots"] > 0
    assert 0 <= c["scan_slots_past_end"] <= c["scan_slots"]
    assert c["h2d_bytes"] > 0 and c["d2h_bytes"] > 0
    # every call materialises fresh job lists, so each packs at least once
    assert 1 <= c["pack_builds"] <= c["pack_profile_tables"] <= c["pack_jobs"]


@pytest.mark.parametrize("call", sorted(SCAN_CALLS))
def test_scan_results_bit_equal_with_profiler(scan_profiles, call):
    plain, profiled, _, _ = scan_profiles[call]
    assert profiled == plain


def test_tile_with_differing_profilers_splits_decide():
    """Members of one vmapped tile with their own profilers (or none)
    each get an even share of the tile's decide time and count the
    tile's slots; no spans open, and results do not change."""
    from repro.core import SimCase, simulate_many

    mat = tiny()

    def cases(tels):
        return [SimCase(jobs=mat.eval_jobs, ci=mat.ci, cluster=mat.cluster,
                        policy=baselines.WaitAwhilePolicy(), t0=mat.t0,
                        horizon=WEEK, engine="scan", telemetry=t)
                for t in tels]

    plain = [r.to_dict() for r in simulate_many(cases([None] * 3))]
    p1, p2 = PhaseProfiler(), PhaseProfiler()
    got = simulate_many(cases([Telemetry(profiler=p1), None,
                               Telemetry(profiler=p2)]))
    assert [r.to_dict() for r in got] == plain
    for p in (p1, p2):
        assert p.calls["decide"] == 1
        assert not any(n.startswith("decide/") for n in p.spans)
        assert {"pack", "policy_tables", "build"} <= set(p.spans)
    assert p1.seconds["decide"] == p2.seconds["decide"]
    assert p1.counters["scan_slots"] == p2.counters["scan_slots"] > 0
    assert p1.counters["d2h_bytes"] == p2.counters["d2h_bytes"] > 0


def test_run_and_sweep_surface_phase_profile():
    from repro.experiment import run

    tel = Telemetry(profiler=PhaseProfiler())
    run(Scenario(capacity=8, learn_weeks=1, family="alibaba", seed=101),
        ["carbon-agnostic", "wait-awhile"], telemetry=tel)
    secs = tel.profiler.seconds
    assert {"provision", "decide", "execute"} <= set(secs)
    assert all(v >= 0 for v in secs.values())


def test_explain_report_sections():
    mat = tiny()
    tel = Telemetry(recorder=MemoryRecorder(), profiler=PhaseProfiler())
    base = simulate(mat.eval_jobs, mat.ci, mat.cluster,
                    baselines.CarbonAgnosticPolicy(), t0=mat.t0,
                    horizon=WEEK, engine="vector")
    res = simulate(mat.eval_jobs, mat.ci, mat.cluster,
                   baselines.WaitAwhilePolicy(), t0=mat.t0, horizon=WEEK,
                   engine="vector", telemetry=tel)
    report = explain(res, baseline=base, recorder=tel.recorder,
                     profiler=tel.profiler)
    assert "run: wait-awhile" in report
    assert "attribution:" in report
    assert "events:" in report
    assert "admit" in report
    assert "phases:" in report


def test_oracle_gap_rows_carry_gap_attribution():
    from repro.experiment import OracleGap, sigma_ladder

    res = OracleGap(base=Scenario(capacity=8, learn_weeks=1,
                                  family="alibaba", seed=101),
                    policies=("wait-awhile",), seeds=(11,),
                    forecasts=sigma_ladder((0.0,))).run()
    rows = res.rows()
    assert rows
    for r in rows:
        att = r["gap_attribution_pp"]
        assert abs(sum(att.values()) - r["gap_pp"]) < 0.02  # rounding only
    s = res.summary()["perfect"]["wait-awhile"]
    assert "gap_attribution_mean_pp" in s
