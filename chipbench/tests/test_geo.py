"""The geo cell: the plain geo reference against the program, the cycle
of worlds a run sends, and a warm-up that compiles every world's shapes.
On the CPU at a small size (two regions of 20 or 24 servers)."""
import copy
import dataclasses
import time

import pytest

from chipbench import compare, generator, harness
from chipbench.reference import Reference
from chipbench.reference import world as rworld
from chipbench.tests.small import small_files

CELL = "geo.whatif"
POLICIES = ("geo-static", "geo-greedy", "geo-flex")


def _files(capacity: int = 20):
    return small_files(CELL, capacity=capacity)


def test_reference_matches_program_on_every_geo_policy():
    _, (_, config, traffic) = _files()
    plan = generator.make_plan(config, traffic, 2 ** 31 + 7)
    assert plan.policies == POLICIES
    out = generator.Requests(plan).send()
    d = compare.digest(out, plan)
    assert sorted(d["results"]) == [0, 1, 2]
    ref = Reference(plan)
    tally = compare.check_request(d, plan, ref)
    assert tally.passed(), (tally.values, tally.notes)
    for i, name in enumerate(POLICIES):
        prog, want = d["results"][i], ref.result(i)
        assert prog["policy"] == want["policy"] == name
        assert prog["migrations"] == want["migrations"]
        assert prog["final_region"] == want["final_region"]
    assert ref.result(0)["migrations"] == 0
    assert ref.result(2)["migrations"] >= 1      # geo-flex moves jobs
    assert ref.result(2)["migration_carbon_g"] > 0


def test_geo_layout_and_migration_costs_are_compared():
    """A program whose migration costs differ from the configuration's,
    or whose result lost a region's totals, fails the check."""
    _, (_, config, traffic) = _files()
    plan = generator.make_plan(config, traffic, 31)
    out = generator.Requests(plan).send()
    mat = out["scenario"].materialize()
    ref = Reference(plan)
    world = ref.world(plan.worlds[0])
    assert compare.world_mismatch(mat, world, ref.migration)[0] == 0
    other = dataclasses.replace(ref.migration, base_slots=2)
    assert compare.world_mismatch(mat, world, other)[0] == 1
    res = out["results"][2].to_dict(include_per_job=True, include_slots=True)
    res["region_carbon_g"] = res["region_carbon_g"][:1]
    t = compare.Tally()
    t.result(res, ref.result(2), "geo-flex")
    assert t.values["decision_mismatch"] == 1 and not t.passed()


def test_run_cycles_its_worlds():
    """Request n takes world n mod W; result i of it is policy i of that
    world, and the reference keys its results by (world, policy)."""
    bench = harness.load_benchmark()
    _, config, traffic = harness.cell_files(bench, CELL)
    plan = generator.make_plan(config, traffic, 2 ** 31 + 11)
    W = traffic["worlds_per_run"]
    assert plan.cycle == W >= 8
    assert len({w.seed for w in plan.worlds}) == W
    assert all(w.regions == ("south-australia", "california")
               for w in plan.worlds)
    assert all(w.scenario_kwargs()["regions"] == w.regions
               and "region" not in w.scenario_kwargs() for w in plan.worlds)
    lo, hi = config["eval_jobs_band"]
    for w in plan.worlds:
        assert lo <= rworld.eval_job_count(
            family=w.family, capacity=w.capacity,
            utilization=w.utilization, learn_weeks=w.learn_weeks,
            eval_weeks=w.eval_weeks, seed=w.seed) <= hi
    for n in range(20):
        assert plan.compared(n) == [0, 1, 2]
        for i in range(3):
            assert plan.cell(i, n) == (plan.worlds[n % W], POLICIES[i])
    assert plan.units_per_request == 3

    _, (_, small, straffic) = _files()
    splan = generator.make_plan(small, straffic, 5)
    ref = Reference(splan)
    assert ref.result(1, 0) is ref.result(1, 2)
    assert ref.result(1, 0) is not ref.result(1, 1)
    assert len(ref.computed) == 2


def _straddling_seed(config, traffic) -> int:
    """A seed whose two worlds' evaluated weeks pad to different row
    counts, so each compiles programs of its own shapes."""
    from repro.core.scan_engine import _pad_rows

    for seed in range(1, 200):
        plan = generator.make_plan(config, traffic, seed)
        pads = {_pad_rows(len(rworld.build_world(**w.world_kwargs())
                              .eval_jobs)) for w in plan.worlds}
        if len(pads) == 2:
            return seed
    raise AssertionError("no seed with two row pads")


@pytest.mark.parametrize("cycle", [None, 1])
def test_warm_up_compiles_every_worlds_shapes(monkeypatch, cycle):
    """Warm-up sends the whole cycle, so no world compiles inside the
    window; warming up on the first world alone would."""
    import jax

    bench, (cell, config, traffic) = _files(capacity=24)
    seed = _straddling_seed(config, traffic)
    jax.clear_caches()          # nothing compiled by an earlier test
    if cycle is not None:
        monkeypatch.setattr(generator.Plan, "cycle", property(
            lambda self: cycle))
    r = harness.measure(CELL, seed, 0.5, False, t_start=time.perf_counter(),
                        require_tpu=False, bench=bench,
                        files=(cell, copy.deepcopy(config), traffic),
                        log=lambda m: None)
    compiles = r["compared"]["window_compiles"]["value"]
    assert r["compared"]["delegated_cases"]["value"] == 0
    if cycle is None:
        assert compiles == 0 and r["correct"] is True
    else:
        assert compiles > 0 and r["correct"] is False


def test_geo_scan_program_is_read():
    ctx = {"call": "run", "units": 30, "window_s": 10.0, "traced_units": 3,
           "phases": {}, "device": {"program_s": {"jit__geo_chunk": 0.3},
                                    "busy_s": 0.3, "window_s": 1.0}}
    assert harness.metric_reader("scan_device_ms.run")(ctx) == \
        pytest.approx(100.0)
