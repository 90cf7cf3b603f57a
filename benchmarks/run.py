"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One benchmark per paper table/figure (see figures.ALL).  Prints
``name,us_per_call,derived`` CSV.  Results are cached in results/bench/ —
pass ``--force`` to recompute, ``--only fig6`` to filter.  A crashing
figure is reported and the rest still run; the process exits non-zero at
the end if any failed.  The chip benchmark is ``chipbench/run.py``.
"""
from __future__ import annotations

import argparse
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    failures = _figure_suite(args)
    if failures:
        print(f"FAILED figures: {', '.join(failures)}", file=sys.stderr)
        sys.exit(1)


def _figure_suite(args) -> list[str]:
    """Run the figures and print their CSV rows; returns those that failed."""
    from . import figures
    from .common import RESULTS_DIR, cached, csv_rows

    failures: list[str] = []
    print("name,us_per_call,derived")
    for name, fn in figures.ALL.items():
        if args.only and args.only not in name:
            continue
        try:
            res = cached(name, lambda fn=fn: fn(), force=args.force)
        except Exception as e:  # noqa: BLE001
            print(f"{name},0,ERROR={e!r}", file=sys.stderr)
            failures.append(name)
            continue
        if name == "tab_overheads":
            for k, v in res.items():
                if not k.startswith("_"):
                    print(f"{name}/{k},{float(v) * 1e6:.0f},seconds={v}")
            continue
        if name == "resilience":
            for sec in ("degradation", "stale_feed"):
                for regime, pols in res[sec].items():
                    for pol, s in pols.items():
                        print(f"{name}/{sec}/{regime}/{pol},0,"
                              f"savings={s['savings_mean_pct']}%"
                              f";viol={s['violation_rate']}"
                              f";lost={s.get('lost_work_slots', 0)}")
            csv = res.get("csv")
            if csv:
                for sec, text in csv.items():
                    path = os.path.join(RESULTS_DIR,
                                        f"resilience_{sec}.csv")
                    with open(path, "w") as f:
                        f.write(text)
                    print(f"{name}/{sec},0,csv={path}")
            else:
                print(f"{name},0,csv=missing (stale cache; rerun with "
                      f"--force to regenerate per-cell tables)",
                      file=sys.stderr)
            continue
        if name == "attribution":
            for family, runs in res.items():
                if family.startswith("_") or family == "csv":
                    continue
                for d in runs:
                    top = max(d["causes"], key=lambda c: abs(d["causes"][c]))
                    print(f"{name}/{family}/seed{d['seed']},0,"
                          f"savings={round(d['savings_pct'], 2)}%"
                          f";top_cause={top}"
                          f";top_g={d['causes'][top]:.1f}")
            csv = res.get("csv")
            if csv:
                path = os.path.join(RESULTS_DIR, "attribution.csv")
                with open(path, "w") as f:
                    f.write(csv)
                print(f"{name},0,csv={path}")
            else:
                print(f"{name},0,csv=missing (stale cache; rerun with "
                      f"--force to regenerate per-run tables)",
                      file=sys.stderr)
            continue
        if name == "forecast_gap":
            for fc, pols in res["summary"].items():
                for pol, s in pols.items():
                    print(f"{name}/{fc}/{pol},0,"
                          f"savings={s['savings_mean_pct']}%"
                          f";gap={s['gap_mean_pp']}pp")
            continue
        for row in csv_rows(name, res):
            print(row)
    return failures


if __name__ == "__main__":
    main()
