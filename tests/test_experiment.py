"""Declarative experiment API: registry, Scenario, driver, Sweep.

Covers the ISSUE-2 acceptance points: every registered policy constructs
through ``make_policy`` and completes a tiny scenario; ``simulate_many``
batches are identical to per-case ``simulate`` runs; a sweep packs each
scenario's jobs exactly once and its JSON round-trips; ``learn_window``
takes a ``ClusterConfig`` (loose form deprecated) and reports which replay
offsets contributed."""
import json

import numpy as np
import pytest

import repro.core.simulator as sim_mod
from repro.core import (CarbonService, ClusterConfig, KnowledgeBase,
                        LearnOutcome, baselines, learn_window, simulate,
                        synthesize_trace)
from repro.core.simulator import FaultModel, SimCase, simulate_many
from repro.experiment import (Scenario, Sweep, SweepResult, WEEK,
                              available_policies, make_policy,
                              prepare_context, run)
from repro.experiment.registry import PolicyContext

TINY = dict(capacity=8, learn_weeks=1, seed=3, family="alibaba")


@pytest.fixture(scope="module")
def tiny():
    return Scenario(**TINY)


# --- Scenario ----------------------------------------------------------------


class TestScenario:
    def test_materialize_is_cached_and_split_is_consistent(self, tiny):
        a, b = tiny.materialize(), tiny.materialize()
        assert a is b                       # same job lists -> one packing
        assert a.t0 == tiny.learn_weeks * WEEK
        assert all(j.arrival < a.t0 for j in a.hist)
        assert all(a.t0 <= j.arrival < tiny.hours for j in a.eval_jobs)
        assert len(a.ci) >= tiny.hours

    def test_eval_shift_regenerates_only_eval_weeks(self):
        plain = Scenario(**TINY).materialize()
        shifted = Scenario(**TINY, eval_shift=0.2).materialize()
        assert [j.job_id for j in plain.hist] == [j.job_id for j in shifted.hist]
        assert len(shifted.eval_jobs) != len(plain.eval_jobs) or \
            any(a.length != b.length for a, b in
                zip(plain.eval_jobs, shifted.eval_jobs))

    def test_unknown_region_raises_value_error(self):
        with pytest.raises(ValueError, match="nowhere.*california"):
            Scenario(region="nowhere")
        with pytest.raises(ValueError, match="nowhere"):
            synthesize_trace("nowhere", 24)
        with pytest.raises(ValueError, match="nowhere"):
            CarbonService.synthetic("nowhere", 24)

    def test_to_dict_round_trip(self):
        sc = Scenario(**TINY, faults=FaultModel(straggler_rate=0.1, seed=4))
        rt = Scenario.from_dict(json.loads(json.dumps(sc.to_dict())))
        assert rt.region == sc.region and rt.seed == sc.seed
        assert rt.faults.straggler_rate == 0.1


# --- registry ----------------------------------------------------------------


class TestRegistry:
    def test_all_policies_complete_tiny_scenario(self, tiny):
        """Round-trip: every registered single-region policy constructs via
        make_policy and completes the tiny scenario without error (geo
        policies run on geo scenarios — tests/test_geo.py — dag policies
        on DAG scenarios — tests/test_dag.py — and serve policies on
        serving scenarios — tests/test_serving.py)."""
        from repro.experiment.registry import get_spec

        names = available_policies()
        assert set(names) >= {"carbon-agnostic", "gaia", "wait-awhile",
                              "carbonscaler", "vcc", "vcc-scaling",
                              "carbonflex", "carbonflex-mpc", "oracle",
                              "geo-static", "geo-greedy", "geo-flex",
                              "dag-fcfs", "dag-carbon", "dag-cap",
                              "serve-static", "serve-greedy", "serve-flex"}
        names = tuple(n for n in names
                      if not get_spec(n).geo and not get_spec(n).dag
                      and not get_spec(n).serve)
        res = run(tiny, names)
        for name in names:
            assert len(res.weekly[name]) == 1, name
            r = res.weekly[name][0]
            assert r.carbon_g > 0, name
            assert (r.completion >= 0).all(), name

    def test_kb_policy_requires_learning(self, tiny):
        mat = tiny.materialize()
        ctx = PolicyContext(cluster=mat.cluster, ci=mat.ci)
        with pytest.raises(ValueError, match="KnowledgeBase"):
            make_policy("carbonflex", ctx)

    def test_unknown_policy_lists_registered(self, tiny):
        mat = tiny.materialize()
        ctx = PolicyContext(cluster=mat.cluster, ci=mat.ci)
        with pytest.raises(ValueError, match="carbon-agnostic"):
            make_policy("not-a-policy", ctx)


# --- driver ------------------------------------------------------------------


class TestDriver:
    def test_carbonflex_beats_agnostic_through_driver(self, tiny):
        res = run(tiny, ["carbon-agnostic", "carbonflex", "oracle"])
        assert res.kb_size == tiny.learn_weeks * WEEK
        assert res.savings("carbonflex") > 0.0
        assert res.savings("oracle") >= res.savings("carbonflex") - 5.0
        m = res.metrics()
        assert m["carbonflex"]["savings_pct"] == pytest.approx(
            res.savings("carbonflex"), abs=0.01)

    def test_continuous_learning_grows_kb_weekly(self):
        sc = Scenario(**{**TINY, "seed": 5}, eval_weeks=2)
        res = run(sc, ["carbon-agnostic", "carbonflex"])
        # initial learn week + one re-learned evaluated week
        assert res.kb_size == 2 * WEEK
        assert len(res.weekly["carbonflex"]) == 2

    def test_faulty_scenario_runs_with_fresh_fault_streams(self):
        sc = Scenario(**{**TINY, "seed": 6},
                      faults=FaultModel(straggler_rate=0.2, seed=9))
        res = run(sc, ["carbon-agnostic"])
        again = run(sc, ["carbon-agnostic"])
        # same seeded fault stream both times -> identical results
        assert res.carbon_g("carbon-agnostic") == again.carbon_g("carbon-agnostic")


# --- simulate_many parity ----------------------------------------------------


class TestBatchParity:
    NAMES = ["carbon-agnostic", "wait-awhile", "carbonscaler", "carbonflex"]

    def test_simulate_many_equals_per_case_simulate(self, tiny):
        mat = tiny.materialize()
        ctx = prepare_context(mat, self.NAMES)
        cases = [SimCase(jobs=mat.eval_jobs, ci=mat.ci, cluster=mat.cluster,
                         policy=make_policy(n, ctx), t0=mat.t0, horizon=WEEK,
                         label=n) for n in self.NAMES]
        batch = simulate_many(cases)
        for n, r in zip(self.NAMES, batch):
            solo = simulate(mat.eval_jobs, mat.ci, mat.cluster,
                            make_policy(n, ctx), t0=mat.t0, horizon=WEEK)
            assert solo.carbon_g == r.carbon_g, n
            np.testing.assert_array_equal(solo.wait_slots, r.wait_slots, err_msg=n)
            np.testing.assert_array_equal(solo.violations, r.violations, err_msg=n)

    def test_parity_holds_under_faults(self, tiny):
        mat = tiny.materialize()
        ctx = prepare_context(mat, ["carbon-agnostic"])
        mk_faults = lambda: FaultModel(straggler_rate=0.15,  # noqa: E731
                                       failure_rate=0.05, seed=2)
        [r] = simulate_many([SimCase(
            jobs=mat.eval_jobs, ci=mat.ci, cluster=mat.cluster,
            policy=make_policy("carbon-agnostic", ctx), t0=mat.t0,
            horizon=WEEK, faults=mk_faults())])
        solo = simulate(mat.eval_jobs, mat.ci, mat.cluster,
                        make_policy("carbon-agnostic", ctx), t0=mat.t0,
                        horizon=WEEK, faults=mk_faults())
        assert solo.carbon_g == r.carbon_g
        np.testing.assert_array_equal(solo.wait_slots, r.wait_slots)
        np.testing.assert_array_equal(solo.violations, r.violations)


@pytest.mark.parametrize("engine", ["scalar", "vector", "scan"])
def test_simulate_many_honours_engine(monkeypatch, engine):
    """``Scenario.engine`` picks the engine every evaluated case of
    ``run()`` runs on, and no other engine runs."""
    from repro.core import scan_engine

    ran = []

    def spy(name, mod):
        real = getattr(mod, name)

        def wrapped(*a, **kw):
            ran.append(name)
            return real(*a, **kw)

        monkeypatch.setattr(mod, name, wrapped)

    for name in ("_simulate_scalar", "_simulate_vector"):
        spy(name, sim_mod)
    spy("simulate_many_scan", scan_engine)
    names = ("carbon-agnostic", "wait-awhile")
    res = run(Scenario(**TINY, engine=engine), names)
    assert all(len(res.weekly[n]) == 1 for n in names)
    expect = {"scalar": "_simulate_scalar", "vector": "_simulate_vector",
              "scan": "simulate_many_scan"}[engine]
    assert ran and set(ran) == {expect}


# --- Sweep -------------------------------------------------------------------


class TestSweep:
    def test_grid_packs_once_per_scenario_and_round_trips(self, monkeypatch):
        packs = []
        orig = sim_mod.PackedJobs

        class CountingPackedJobs(orig):
            def __init__(self, jobs_sorted):
                packs.append(len(jobs_sorted))
                super().__init__(jobs_sorted)

        monkeypatch.setattr(sim_mod, "PackedJobs", CountingPackedJobs)
        sweep = Sweep(
            base=Scenario(capacity=8, learn_weeks=1, family="alibaba"),
            regions=["california", "ontario"], seeds=[31, 32],
            policies=["carbon-agnostic", "wait-awhile", "gaia", "carbonflex"])
        sr = sweep.run()
        # 2 regions x 2 seeds -> 4 scenarios, each packed exactly once
        # even though each runs 4 policies
        assert len(packs) == 4
        assert len(sr.rows()) == 16

        base_rows = [r for r in sr.rows() if r["policy"] == "carbon-agnostic"]
        assert all(r["savings_pct"] == 0.0 for r in base_rows)
        flex = [r for r in sr.rows() if r["policy"] == "carbonflex"]
        assert {(r["region"], r["seed"]) for r in flex} == \
            {("california", 31), ("california", 32),
             ("ontario", 31), ("ontario", 32)}

        payload = sr.to_json()
        restored = SweepResult.from_json(payload)
        assert restored.to_json() == payload
        assert restored.summary()["carbonflex"]["n_cases"] == 4

    def test_to_csv_header_is_union_over_mixed_row_shapes(self):
        """ISSUE-8 satellite: heterogeneous sweeps (fault axes where only
        some rows carry resilience metrics, serving rows with nested
        dicts, columns that first appear mid-list) must export as one
        rectangular CSV — header = first-seen-order union of every row's
        flattened keys, missing cells empty."""
        import csv
        import io

        rows = [
            {"region": "ontario", "seed": 1, "policy": "a", "carbon_g": 10.0},
            {"region": "ontario", "seed": 1, "policy": "b", "carbon_g": 9.0,
             "resilience": {"evictions": 3, "lost_work_slots": 1.5}},
            {"region": "texas", "seed": 2, "policy": "a", "carbon_g": 8.0,
             "forecast": "noisy", "tiers": ["full", "half"]},
        ]
        csv_text = SweepResult(baseline="a", rows_=rows).to_csv()
        lines = csv_text.splitlines()
        assert lines[0].split(",") == [
            "region", "seed", "policy", "carbon_g",
            "resilience.evictions", "resilience.lost_work_slots",
            "forecast", "tiers"]
        parsed = list(csv.DictReader(io.StringIO(csv_text)))
        assert len(parsed) == 3
        # rows missing a column get empty cells, not dropped columns
        assert parsed[0]["resilience.evictions"] == ""
        assert parsed[1]["resilience.evictions"] == "3"
        assert parsed[0]["forecast"] == "" and parsed[2]["forecast"] == "noisy"
        # list values join with | so the table stays one value per cell
        assert parsed[2]["tiers"] == "full|half"
        assert all(len(line.split(",")) == 8 for line in lines)

    def test_base_scenario_faults_inherited(self):
        base = Scenario(capacity=8, learn_weeks=1, family="alibaba", seed=51)
        faulty = Scenario(capacity=8, learn_weeks=1, family="alibaba", seed=51,
                          faults=FaultModel(straggler_rate=0.4,
                                            failure_rate=0.1, seed=7))
        clean = Sweep(base=base, policies=["carbon-agnostic"]).run()
        injected = Sweep(base=faulty, policies=["carbon-agnostic"]).run()
        assert injected.rows()[0]["fault"] != "none"
        assert injected.rows()[0]["carbon_g"] != clean.rows()[0]["carbon_g"]

    def test_baseline_added_when_missing(self):
        sweep = Sweep(base=Scenario(capacity=8, learn_weeks=1,
                                    family="alibaba", seed=41),
                      policies=["wait-awhile"])
        sr = sweep.run()
        assert {r["policy"] for r in sr.rows()} == \
            {"carbon-agnostic", "wait-awhile"}
        assert all("savings_pct" in r for r in sr.rows())


# --- learn_window surface ----------------------------------------------------


class TestLearnWindow:
    def _world(self):
        cluster = ClusterConfig.default(capacity=10)
        ci = CarbonService.synthetic("ontario", WEEK * 3, seed=17)
        from repro.traces import TraceSpec, generate_trace

        jobs = generate_trace(TraceSpec(family="alibaba", hours=WEEK,
                                        capacity=10, seed=18), cluster.queues)
        return cluster, ci, jobs

    def test_cluster_config_form_reports_contributing_offsets(self):
        cluster, ci, jobs = self._world()
        kb = KnowledgeBase()
        # the middle offset's window holds no arrivals (trace spans 1 week)
        out = learn_window(kb, jobs, ci, 0, WEEK, cluster,
                           offsets=(0, WEEK, 0), backend="numpy")
        assert isinstance(out, LearnOutcome)
        assert out.contributed == (0, 0)
        assert out.empty == (WEEK,)
        assert len(out) == 2 and len(list(out)) == 2   # list-compat
        assert len(kb) == 2 * WEEK

    def test_deprecated_loose_form_still_works_and_warns(self):
        cluster, ci, jobs = self._world()
        kb_new, kb_old = KnowledgeBase(), KnowledgeBase()
        learn_window(kb_new, jobs, ci, 0, WEEK, cluster, backend="numpy")
        with pytest.warns(DeprecationWarning, match="ClusterConfig"):
            learn_window(kb_old, jobs, ci, 0, WEEK, cluster.capacity,
                         len(cluster.queues), backend="numpy")
        assert len(kb_old) == len(kb_new)

    def test_cluster_config_plus_num_queues_rejected(self):
        cluster, ci, jobs = self._world()
        with pytest.raises(TypeError, match="implied"):
            learn_window(KnowledgeBase(), jobs, ci, 0, WEEK, cluster, 3)
        with pytest.raises(TypeError, match="num_queues"):
            learn_window(KnowledgeBase(), jobs, ci, 0, WEEK, cluster.capacity)


# --- SimResult serialization -------------------------------------------------


def test_sim_result_to_dict_json_safe(tiny):
    mat = tiny.materialize()
    r = simulate(mat.eval_jobs, mat.ci, mat.cluster,
                 baselines.CarbonAgnosticPolicy(), t0=mat.t0, horizon=WEEK)
    d = r.to_dict()
    assert set(d) == {"policy", "carbon_g", "energy_kwh", "num_jobs",
                      "mean_wait", "violation_rate"}
    full = r.to_dict(include_per_job=True, include_slots=True)
    assert len(full["completion"]) == r.num_jobs
    assert len(full["slots"]) == len(r.slots)
    json.dumps(full)            # everything JSON-serialisable
