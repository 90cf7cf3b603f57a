"""The recorded cells plan, send and read exactly as recorded in
``fixtures/plans.json`` (``record_plan_fixture.py``): every world of
their plans at three seeds, the ``Scenario`` keywords each request
sends, the results each request compares and whose they are, and what
every per-layer reader gives on a traced single-region context.  A
harness that learns a new kind of deployment leaves these unchanged."""
import json

import pytest

from chipbench import generator, harness
from chipbench.tests import record_plan_fixture as rec

with open(rec.PATH) as f:
    RECORDED = json.load(f)


@pytest.mark.parametrize("cell", rec.CELLS)
@pytest.mark.parametrize("seed", rec.SEEDS)
def test_plan_unchanged(cell, seed):
    _, config, traffic = harness.cell_files(harness.load_benchmark(), cell)
    plan = generator.make_plan(config, traffic, seed)
    got = json.loads(json.dumps(rec.plan_record(plan)))
    assert got == RECORDED["plans"][cell][str(seed)]
    # a run that cycles W worlds sends request n to world n mod W; every
    # other cell's requests are alike
    assert plan.cycle == traffic.get("worlds_per_run", 1)
    for n in range(rec.REQUESTS):
        for i in range(plan.units_per_request):
            assert plan.cell(i, n) == (
                plan.cell(i) if plan.cycle == 1 else
                (plan.worlds[n % plan.cycle], plan.policies[i]))


@pytest.mark.parametrize("name", sorted(RECORDED["readers"]))
def test_reader_unchanged(name):
    call = name.rsplit(".", 1)[1]
    assert harness.metric_reader(name)(rec.reader_ctx(call)) == \
        RECORDED["readers"][name]
