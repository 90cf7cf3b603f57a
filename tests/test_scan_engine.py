"""Scan-engine internals (ISSUE-8): native-kind dispatch, the dependency
gating's two forms, delegation boundaries, and the vmapped
batch tile — every path asserted bit-identical to the scalar reference.

Cross-engine *end-to-end* parity per policy family lives in
``test_engine_parity.py`` / ``test_geo.py`` / ``test_dag.py`` /
``test_resilience.py``; this file pins the scan engine's own moving
parts: which cases run natively vs delegate, that the gather-form
dependency decrement equals the scatter form exactly, and
that ``simulate_many`` fusing structurally identical scan cases into one
vmapped program returns the same bytes as running them one at a time.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CarbonService, ClusterConfig, GeoCluster,
                        GeoFlexPolicy, GeoStaticPolicy,
                        MultiRegionCarbonService, baselines, simulate)
from repro.core.dag import DagCapPolicy, DagCarbonPolicy, DagFcfsPolicy
from repro.core.faults import CarbonDataOutage, FaultModel
from repro.core.forecast import NoisyForecast, QuantileForecast
from repro.core.scan_engine import native_kind
from repro.core.simulator import SimCase, simulate_many
from repro.kernels import gating
from repro.traces import (DagConfig, TraceSpec, generate_dag_trace,
                          generate_trace)

WEEK = 24 * 7


def assert_identical(a, b, ctx=""):
    assert a.carbon_g == b.carbon_g, ctx
    assert a.energy_kwh == b.energy_kwh, ctx
    np.testing.assert_array_equal(a.completion, b.completion, err_msg=ctx)
    np.testing.assert_array_equal(a.violations, b.violations, err_msg=ctx)
    np.testing.assert_array_equal(a.wait_slots, b.wait_slots, err_msg=ctx)
    for la, lb in zip(a.slots, b.slots):
        assert la == lb, f"{ctx}: slot {la.slot}"


# --- native-kind dispatch -----------------------------------------------------


def test_native_kind_dispatch():
    cluster = ClusterConfig.default(capacity=8)
    geo = GeoCluster.split(8, ("ontario", "california"))
    assert native_kind(baselines.CarbonAgnosticPolicy(), cluster, None) == "plain"
    assert native_kind(DagFcfsPolicy(), cluster, None) == "plain"
    assert native_kind(baselines.WaitAwhilePolicy(), cluster, None) == "thresh"
    assert native_kind(baselines.RobustWaitAwhilePolicy(), cluster, None) == "thresh"
    assert native_kind(DagCarbonPolicy(), cluster, None) == "thresh"
    assert native_kind(DagCapPolicy(), cluster, None) == "cap"
    assert native_kind(GeoStaticPolicy(), geo, None) == "geo-static"
    assert native_kind(GeoFlexPolicy(), geo, None) == "geo-flex"
    # unknown policies and any fault process delegate to the vector engine
    assert native_kind(baselines.GaiaPolicy(mean_length=2.0), cluster, None) is None
    assert native_kind(baselines.CarbonAgnosticPolicy(), cluster,
                       FaultModel(straggler_rate=0.1, seed=1)) is None

    class Tweaked(baselines.WaitAwhilePolicy):
        pass

    # exact type() checks: a subclass may override decide()
    assert native_kind(Tweaked(), cluster, None) is None


# --- dependency gating: scatter == gather ------------------------------------


@pytest.mark.parametrize("seed", [0, 3, 11])
@pytest.mark.parametrize("n_edges", [0, 17, 2048])
def test_dep_decrement_scatter_gather_parity(seed, n_edges):
    """The scatter-form jnp decrement and the transposed gather form the
    scan engine prefers on CPU must return the same int32 counts on
    random edge sets (integer addition: exact in any order)."""
    rng = np.random.default_rng(seed)
    n = 256  # row n-1 is padding and never finishes
    fin = np.zeros(n, dtype=bool)
    fin[:n - 1] = rng.random(n - 1) < 0.4
    parents = rng.integers(0, n - 1, size=n_edges)
    children = rng.integers(0, n - 1, size=n_edges)
    # padded transpose: each row's predecessor list, padding -> row n-1
    deg = np.bincount(children, minlength=n)
    d_pad = max(1, int(deg.max()) if n_edges else 1)
    pred_rows = np.full((n, d_pad), n - 1, dtype=np.int64)
    order = np.argsort(children, kind="stable")
    starts = np.concatenate([[0], np.cumsum(deg)])
    sc = children[order]
    pred_rows[sc, np.arange(len(sc)) - starts[sc]] = parents[order]

    fin_j = jnp.asarray(fin)
    scatter = gating.dep_decrement(fin_j, jnp.asarray(parents),
                                   jnp.asarray(children), n)
    gather = gating.dep_decrement_gather(fin_j, jnp.asarray(pred_rows))
    np.testing.assert_array_equal(np.asarray(scatter), np.asarray(gather))


# --- scan-native parity off the fast paths ------------------------------------


def _single_world(seed=31):
    cluster = ClusterConfig.default(capacity=12)
    ci = CarbonService.synthetic("germany", WEEK * 2 + 24 * 30, seed=seed)
    spec = TraceSpec(family="azure", hours=WEEK, capacity=12, seed=seed + 1)
    jobs = generate_trace(spec, cluster.queues)
    return cluster, ci, jobs


@pytest.mark.parametrize("policy_cls", [baselines.CarbonAgnosticPolicy,
                                        baselines.WaitAwhilePolicy])
def test_scan_parity_under_feed_outage(policy_cls):
    """An outage-degraded CI view disables every batched table fast path
    (the view is a DegradedCIView, not a plain CarbonService) — the scan
    engine must stay native and still match the scalar engine bit-for-bit
    through the per-slot fallback."""
    cluster, ci, jobs = _single_world()
    ci = dataclasses.replace(
        ci, outage=CarbonDataOutage(windows=((10, 40), (80, 100))))
    assert native_kind(policy_cls(), cluster, None) is not None
    rs = simulate(jobs, ci, cluster, policy_cls(), horizon=WEEK,
                  engine="scalar")
    rc = simulate(jobs, ci, cluster, policy_cls(), horizon=WEEK,
                  engine="scan")
    assert_identical(rs, rc, f"outage/{policy_cls.__name__}")


@pytest.mark.parametrize("forecast", [NoisyForecast(sigma=0.25, seed=7),
                                      QuantileForecast(sigma=0.2, seed=7,
                                                       members=5)])
def test_scan_parity_native_under_forecast_models(forecast):
    """Non-perfect forecast models also bypass the batched eligibility
    table; the per-slot fallback must consume the realized error stream
    exactly like the scalar engine (same RNG order, same floats)."""
    cluster, ci, jobs = _single_world(seed=5)
    ci = dataclasses.replace(ci, model=forecast)
    rs = simulate(jobs, ci, cluster, baselines.WaitAwhilePolicy(),
                  horizon=WEEK, engine="scalar")
    rc = simulate(jobs, ci, cluster, baselines.WaitAwhilePolicy(),
                  horizon=WEEK, engine="scan")
    assert_identical(rs, rc, f"forecast/{forecast!r}")


def test_scan_parity_dag_cap_gather_and_scatter_paths():
    """Precedence gating runs through the gather-form decrement for
    ordinary in-degrees; wide fan-in workloads keep the scatter form.
    Both must match the scalar engine exactly."""
    cluster = ClusterConfig.default(capacity=10)
    ci = CarbonService.synthetic("poland", WEEK * 2 + 24 * 30, seed=9)
    spec = TraceSpec(family="azure", hours=WEEK, capacity=10,
                     utilization=0.4, seed=10)
    for dag in (DagConfig(width=3, depth=4),          # gather path
                DagConfig(width=80, depth=2)):        # scatter fallback
        jobs = generate_dag_trace(spec, dag, cluster.queues)
        for policy_cls in (DagCarbonPolicy, DagCapPolicy):
            rs = simulate(jobs, ci, cluster, policy_cls(), horizon=WEEK,
                          engine="scalar")
            rc = simulate(jobs, ci, cluster, policy_cls(), horizon=WEEK,
                          engine="scan")
            assert_identical(rs, rc, f"{dag.width}x{dag.depth}/"
                                     f"{policy_cls.__name__}")


# --- batched dispatch: one vmapped program == per-case runs -------------------


def test_simulate_many_scan_tile_matches_per_case_runs():
    """simulate_many fuses structurally identical scan cases (same
    packed shape/deps/horizon) into one vmapped tile — mixed policy
    kinds included, since the decision tables live in per-member consts.
    The fused results must equal per-case ``engine="scan"`` runs, which
    in turn equal the scalar reference."""
    cluster, _, jobs = _single_world(seed=17)
    mks = [baselines.CarbonAgnosticPolicy, baselines.WaitAwhilePolicy,
           baselines.RobustWaitAwhilePolicy]
    cases, solo = [], []
    for seed in (0, 1):
        ci = CarbonService.synthetic("texas", WEEK * 2 + 24 * 30, seed=seed)
        for mk in mks:
            cases.append(SimCase(jobs=jobs, ci=ci, cluster=cluster,
                                 policy=mk(), horizon=WEEK, engine="scan",
                                 label=f"s{seed}/{mk.__name__}"))
            solo.append((ci, mk))
    batch = simulate_many(cases)
    assert len(batch) == 6
    for case, res, (ci, mk) in zip(cases, batch, solo):
        one = simulate(jobs, ci, cluster, mk(), horizon=WEEK, engine="scan")
        assert_identical(one, res, f"tile/{case.label}")
        ref = simulate(jobs, ci, cluster, mk(), horizon=WEEK,
                       engine="scalar")
        assert_identical(ref, res, f"tile-vs-scalar/{case.label}")


def test_simulate_many_scan_mixed_native_geo_and_delegated():
    """One batch mixing a vmapped-tile case, a geo-native case, a DAG
    case, and a delegating (unknown-policy) case routes each through the
    right path and matches per-case runs."""
    cluster, ci, jobs = _single_world(seed=23)
    geo = GeoCluster.split(12, ("ontario", "sweden"))
    mci = MultiRegionCarbonService.synthetic(
        ("ontario", "sweden"), WEEK * 2 + 24 * 30, seed=3)
    spec = TraceSpec(family="azure", hours=WEEK, capacity=10,
                     utilization=0.4, seed=24)
    dag_jobs = generate_dag_trace(spec, DagConfig(width=3, depth=3),
                                  cluster.queues)
    cases = [
        SimCase(jobs=jobs, ci=ci, cluster=cluster,
                policy=baselines.WaitAwhilePolicy(), horizon=WEEK,
                engine="scan", label="single"),
        SimCase(jobs=jobs, ci=mci, cluster=geo, policy=GeoFlexPolicy(),
                horizon=WEEK, engine="scan", label="geo"),
        SimCase(jobs=dag_jobs, ci=ci, cluster=cluster,
                policy=DagCarbonPolicy(), horizon=WEEK, engine="scan",
                label="dag"),
        SimCase(jobs=jobs, ci=ci, cluster=cluster,
                policy=baselines.GaiaPolicy(mean_length=2.5), horizon=WEEK,
                engine="scan", label="delegated"),
    ]
    batch = simulate_many(cases)
    refs = [
        simulate(jobs, ci, cluster, baselines.WaitAwhilePolicy(),
                 horizon=WEEK, engine="scalar"),
        simulate(jobs, mci, geo, GeoFlexPolicy(), horizon=WEEK,
                 engine="scalar"),
        simulate(dag_jobs, ci, cluster, DagCarbonPolicy(), horizon=WEEK,
                 engine="scalar"),
        simulate(jobs, ci, cluster, baselines.GaiaPolicy(mean_length=2.5),
                 horizon=WEEK, engine="scalar"),
    ]
    for case, res, ref in zip(cases, batch, refs):
        assert_identical(ref, res, f"mixed/{case.label}")


# --- randomized sweep across native kinds -------------------------------------


@pytest.mark.parametrize("seed", range(4))
def test_scan_parity_randomized(seed):
    rng = np.random.default_rng(seed)
    cap = int(rng.integers(4, 16))
    cluster = ClusterConfig.default(capacity=cap)
    ci = CarbonService.synthetic(
        str(rng.choice(["ontario", "texas", "virginia", "sweden"])),
        WEEK * 2 + 24 * 30, seed=seed)
    spec = TraceSpec(family=str(rng.choice(["azure", "alibaba"])),
                     hours=WEEK, capacity=cap,
                     utilization=float(rng.uniform(0.3, 0.8)), seed=seed)
    jobs = generate_trace(spec, cluster.queues)
    for mk in (baselines.CarbonAgnosticPolicy, baselines.WaitAwhilePolicy,
               baselines.RobustWaitAwhilePolicy):
        rs = simulate(jobs, ci, cluster, mk(), horizon=WEEK,
                      engine="scalar")
        rc = simulate(jobs, ci, cluster, mk(), horizon=WEEK, engine="scan")
        assert_identical(rs, rc, f"rand{seed}/{mk.__name__}")
