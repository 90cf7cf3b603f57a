"""Record ``fixtures/plans.json``, what ``test_plans_unchanged.py`` holds
the recorded cells to: for each of them at three seeds, the plan
``generator.make_plan`` builds (every world, the ``Scenario`` keywords
each request sends, the compared indices of its first requests and the
world and policy of every result), and what every per-layer reader
gives on a fixed traced-run context.

Run on the CPU from the root of a checkout:

    JAX_PLATFORMS=cpu python3 chipbench/tests/record_plan_fixture.py

A change to the harness leaves these cells' plans, checks and readings
as they were only where the file it writes stays the same.
"""
import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import generator, harness  # noqa: E402

CELLS = ("single.whatif", "single.sweep-regions", "single.sweep-forecast",
         "geo.whatif")
SEEDS = (12345, 2 ** 31 + 5, 3000000019)
REQUESTS = 4
PATH = os.path.join(HERE, "fixtures", "plans.json")


def reader_ctx(call: str) -> dict:
    """A traced run's context as a single-region cell's trace gives it:
    the two single-region scan programs and one other."""
    return {"call": call, "units": 52, "window_s": 51.25,
            "traced_units": 4 if call == "run" else 64,
            "phases": {"provision": 0.75, "learn": 2.5, "decide": 9.125,
                       "execute": 1.5},
            "device": {"program_s": {"jit__single_chunk": 1.875,
                                     "jit__single_chunk_batch": 0.0625,
                                     "jit_other": 0.5},
                       "busy_s": 2.5, "window_s": 3.75}}


def plan_record(plan) -> dict:
    return {
        "call": plan.call, "policies": list(plan.policies),
        "units_per_request": plan.units_per_request,
        "n_compare": plan.n_compare, "unit": plan.unit, "seed": plan.seed,
        "worlds": [dataclasses.asdict(w) for w in plan.worlds],
        "scenario_kwargs": [w.scenario_kwargs() for w in plan.worlds],
        "compared": [plan.compared(n) for n in range(REQUESTS)],
        "cells": [[plan.worlds.index(plan.cell(i)[0]), plan.cell(i)[1]]
                  for i in range(plan.units_per_request)],
    }


def record() -> dict:
    bench = harness.load_benchmark()
    out = {"plans": {}, "readers": {}}
    for cell in CELLS:
        _, config, traffic = harness.cell_files(bench, cell)
        out["plans"][cell] = {
            str(s): plan_record(generator.make_plan(config, traffic, s))
            for s in SEEDS}
    for m in bench["per_layer"]:
        call = m["name"].rsplit(".", 1)[1]
        out["readers"][m["name"]] = harness.metric_reader(m["name"])(
            reader_ctx(call))
    return json.loads(json.dumps(out))


def main() -> int:
    data = record()
    os.makedirs(os.path.dirname(PATH), exist_ok=True)
    with open(PATH, "w") as f:
        json.dump(data, f, sort_keys=True, separators=(",", ":"))
        f.write("\n")
    print(PATH, os.path.getsize(PATH))
    return 0


if __name__ == "__main__":
    sys.exit(main())
