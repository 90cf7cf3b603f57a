"""Readings that the limits of ``compare.LIMITS`` are set from.

For each seed, in one process: one request through the program's timed
path at the cell's own size, compared with the float64 reference (the
lower readings), and the control, the same reference computed in
float32, compared with the float64 reference on the same cells (the
upper readings).  The benchmark's own runs never run this.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3

prints one JSON line per seed and exits 1 with no line when JAX finds no
TPU.  ``chipbench/tests/test_control.py`` runs the same at a small size
on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# the TPU runtime logs to a fixed /tmp directory unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import compare, generator, harness  # noqa: E402
from chipbench.reference import Reference  # noqa: E402


def control_tally(plan, ref: Reference) -> compare.Tally:
    """The float32 reference in the program's place."""
    low = Reference(plan, dtype=np.float32)
    tally = compare.Tally()
    for i in plan.compared(0):
        tally.result(low.result(i), ref.result(i), f"cell {i}")
    return tally


def readings(config: dict, traffic: dict, seeds, log=print) -> list[dict]:
    rows = []
    for seed in seeds:
        plan = generator.make_plan(config, traffic, seed)
        out = generator.Requests(plan).send()
        ref = Reference(plan)
        prog = compare.check_request(compare.digest(out, plan), plan, ref)
        ctrl = control_tally(plan, ref)
        row = {"seed": seed, "program": prog.values, "control": ctrl.values,
               "notes": prog.notes[:3]}
        log(json.dumps(row))
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell, config, traffic = harness.cell_files(bench, args.workload)
    try:
        harness.device_info(cell["chips"])
    except harness.NoDevice as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    harness.use_compile_cache()
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    readings(config, traffic, [int(s) for s in args.seeds.split(",")],
             log=lambda s: print(s, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
