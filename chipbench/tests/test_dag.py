"""The DAG cell: the plain DAG reference against the program, the cycle of
worlds a run sends, edges held to the reference's, and a warm-up that
compiles every world's shapes.  On the CPU at a small size (20 or 24
servers)."""
import copy
import dataclasses
import time

import pytest

from chipbench import compare, generator, harness
from chipbench.reference import Reference
from chipbench.reference import world as rworld
from chipbench.tests.small import small_files

CELL = "dag.whatif"
POLICIES = ("dag-fcfs", "dag-carbon", "dag-cap")


def _files(capacity: int = 20):
    return small_files(CELL, capacity=capacity)


def test_reference_matches_program_on_every_dag_policy():
    _, (_, config, traffic) = _files()
    plan = generator.make_plan(config, traffic, 2 ** 31 + 7)
    assert plan.policies == POLICIES
    out = generator.Requests(plan).send()
    assert out["kb_size"] == 0          # no DAG policy learns
    d = compare.digest(out, plan)
    assert sorted(d["results"]) == [0, 1, 2]
    ref = Reference(plan)
    tally = compare.check_request(d, plan, ref)
    assert tally.passed(), (tally.values, tally.notes)
    assert tally.values["energy_rel_err"] == 0.0
    for i, name in enumerate(POLICIES):
        prog, want = d["results"][i], ref.result(i)
        assert prog["policy"] == want["policy"] == name
        assert prog["completion"] == want["completion"]
        assert prog["wait_slots"] == want["wait_slots"]
    world = ref.world(plan.worlds[0])
    assert sum(1 for j in world.eval_jobs if j.deps) > len(world.eval_jobs) // 2
    # the policies differ: gating alone, the carbon rule, criticality
    carbon = [ref.result(i)["carbon_g"] for i in range(3)]
    assert carbon[1] < carbon[2] < carbon[0]


def test_edges_are_compared():
    """A program whose materialised world rewires one task's predecessors
    fails the provision check."""
    _, (_, config, traffic) = _files()
    plan = generator.make_plan(config, traffic, 31)
    out = generator.Requests(plan).send()
    mat = out["scenario"].materialize()
    ref = Reference(plan)
    world = ref.world(plan.worlds[0])
    assert compare.world_mismatch(mat, world)[0] == 0
    i = next(i for i, j in enumerate(mat.jobs) if len(j.deps) == 1)
    (parent,) = mat.jobs[i].deps
    jobs = list(mat.jobs)
    jobs[i] = dataclasses.replace(jobs[i], deps=(parent - 1,))
    rewired = dataclasses.replace(mat, jobs=jobs)
    assert compare.world_mismatch(rewired, world)[0] == 1


def test_run_cycles_its_worlds():
    """Request n takes world n mod W of DAG worlds inside the task band;
    result i of it is policy i of that world."""
    bench = harness.load_benchmark()
    _, config, traffic = harness.cell_files(bench, CELL)
    plan = generator.make_plan(config, traffic, 2 ** 31 + 11)
    W = traffic["worlds_per_run"]
    assert plan.cycle == W >= 8
    assert len({w.seed for w in plan.worlds}) == W
    assert plan.dag == config["scenario"]["dag"]
    assert all(w.regions == ("south-australia",)
               and "dag" not in w.scenario_kwargs() for w in plan.worlds)
    lo, hi = config["eval_jobs_band"]
    for w in plan.worlds[:4]:
        assert lo <= rworld.eval_job_count(
            family=w.family, capacity=w.capacity,
            utilization=w.utilization, learn_weeks=w.learn_weeks,
            eval_weeks=w.eval_weeks, seed=w.seed, dag=plan.dag) <= hi
    for n in range(20):
        assert plan.compared(n) == [0, 1, 2]
        for i in range(3):
            assert plan.cell(i, n) == (plan.worlds[n % W], POLICIES[i])
    assert plan.units_per_request == 3
    req = generator.Requests(plan)
    assert req._extra["dag"].width == config["scenario"]["dag"]["width"]


def _straddling_seed(config, traffic) -> int:
    """A seed whose two worlds' evaluated tasks pad to different row
    counts, so each compiles programs of its own shapes."""
    from repro.core.scan_engine import _pad_rows

    for seed in range(1, 200):
        plan = generator.make_plan(config, traffic, seed)
        pads = {_pad_rows(len(rworld.build_world(**w.world_kwargs(),
                                                 dag=plan.dag).eval_jobs))
                for w in plan.worlds}
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
