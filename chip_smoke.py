"""Bring-up smoke of the scan engine on one TPU chip.

Drives the main path, ``Scenario(engine="scan")`` -> ``run()``/``Sweep`` ->
the jitted ``lax.scan`` slot loop on the device, at the paper's scale
(capacity 150, the ``bench_engine --full`` scale) and holds every phase
to the engines' bit-parity contract:

- single-region: the scan-native policies through ``run()``, bit-equal to
  ``engine="scalar"``;
- geo (south-australia + california): geo-static/greedy/flex, bit-equal
  to scalar;
- DAG: dag-fcfs/carbon/cap, bit-equal to scalar;
- vmapped tile: a forecast-error ``Sweep`` whose cells fuse into
  ``BATCH_TILE``-wide vmapped programs, bit-equal to ``engine="vector"``;
- KNN: the knowledge base's batched top-k through the compiled Pallas
  kernel and through the jitted jax path against the numpy path, under a
  float32 tolerance.

A case that the scan engine hands to the vector engine fails the phase.
Wall seconds of the first (compiling) and a warm repeat are printed for
each scan phase; they are a bring-up smoke, not a benchmark.  The last
line of standard output is the JSON result; any failure exits non-zero
without printing it.  There is no CPU fallback.

Usage: python chip_smoke.py
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
CAPACITY = 150


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_check() -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SmokeFailure(f"no TPU: jax.devices()[0] is {d.platform!r} "
                           f"({d.device_kind}); this smoke has no CPU "
                           "fallback")
    print(f"device: {d.platform} {d.device_kind} x{len(devs)}", flush=True)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _timed(fn):
    t = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t


def _full(res) -> dict:
    return res.to_dict(include_per_job=True, include_slots=True)


def _first_diff(a, b, path: str = "") -> str | None:
    """Where two ``to_dict`` trees first differ, or None if equal."""
    if isinstance(a, dict) and isinstance(b, dict):
        if a.keys() != b.keys():
            return f"{path}: keys {sorted(a.keys() ^ b.keys())} differ"
        for k in a:
            d = _first_diff(a[k], b[k], f"{path}.{k}")
            if d:
                return d
        return None
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        if len(a) != len(b):
            return f"{path}: length {len(a)} != {len(b)}"
        for i, (x, y) in enumerate(zip(a, b)):
            d = _first_diff(x, y, f"{path}[{i}]")
            if d:
                return d
        return None
    return None if a == b else f"{path}: {a!r} != {b!r}"


def check_equal(a, b, what: str) -> None:
    d = _first_diff(_full(a), _full(b))
    check(d is None, f"{what}: {d}")


def check_native(name: str, scenario, policies) -> None:
    """Fail if the scan engine would hand any of the phase's cases to the
    vector engine: every policy, built as ``run()``/``Sweep`` build it,
    must be a scan-native kind on this cluster, and the evaluated week
    must hold jobs."""
    from repro.core.scan_engine import native_kind
    from repro.experiment import prepare_context
    from repro.experiment.registry import make_policy

    mat = scenario.materialize()
    check(len(mat.eval_jobs) > 0, f"{name}: no jobs in the evaluated week")
    ctx = prepare_context(mat, policies)
    cluster = mat.geo if mat.is_geo else mat.cluster
    for p in policies:
        check(native_kind(make_policy(p, ctx), cluster,
                          scenario.faults) is not None,
              f"{name}/{p} would be delegated to the vector engine")


def run_phase(name: str, scenario, policies) -> None:
    """``run()`` on the scan engine twice (compile, warm), then the scalar
    reference; every policy-week must be bit-equal."""
    from repro.experiment import run

    check_native(name, scenario, policies)
    first, t_first = _timed(lambda: run(scenario, policies))
    warm, t_warm = _timed(lambda: run(scenario, policies))
    ref, t_ref = _timed(lambda: run(
        dataclasses.replace(scenario, engine="scalar"), policies))
    n = 0
    for p in policies:
        check(len(first.weekly[p]) == len(ref.weekly[p]) > 0,
              f"{name}/{p}: {len(first.weekly[p])} scan weeks vs "
              f"{len(ref.weekly[p])} scalar weeks")
        for w, (a, b, c) in enumerate(zip(first.weekly[p], warm.weekly[p],
                                          ref.weekly[p])):
            check_equal(a, c, f"{name}/{p}/w{w}: scan != scalar")
            check_equal(b, c, f"{name}/{p}/w{w}: warm scan != scalar")
            n += 1
    print(f"{name}: first {t_first:.3f}s warm {t_warm:.3f}s "
          f"(scalar reference {t_ref:.3f}s); {n} policy-weeks bit-equal "
          f"to scalar, none delegated", flush=True)


def sweep_phase(capacity: int, cells: int) -> None:
    """A forecast-error sweep: ``cells`` noisy-forecast cells per policy
    over one world, so each policy's cells are structurally identical and
    fuse into vmapped ``BATCH_TILE`` programs."""
    from repro.core import scan_engine
    from repro.core.forecast import NoisyForecast
    from repro.experiment import Scenario, Sweep

    sigmas = (0.05, 0.1, 0.15, 0.2, 0.25, 0.3, 0.35, 0.4)
    forecasts = [NoisyForecast(sigma=sigmas[i % len(sigmas)],
                               seed=i // len(sigmas)) for i in range(cells)]
    base = Scenario(region="south-australia", capacity=capacity,
                    learn_weeks=1, seed=7, engine="scan")
    names = ("carbon-agnostic", "wait-awhile")
    check_native("sweep", base, names)
    sweep = Sweep(base=base, policies=names, forecasts=forecasts)
    batched = scan_engine._single_chunk_batch
    compiled_before = batched._cache_size()
    first, t_first = _timed(sweep.run)
    check(batched._cache_size() > compiled_before,
          "the sweep never dispatched a vmapped tile")
    warm, t_warm = _timed(sweep.run)
    ref_sweep = dataclasses.replace(
        sweep, base=dataclasses.replace(base, engine="vector"))
    ref, t_ref = _timed(ref_sweep.run)
    check(len(first.rows()) == len(ref.rows()) == 2 * cells,
          f"sweep: {len(first.rows())} scan rows, {len(ref.rows())} vector rows")
    for a, b in zip(first.results, ref.results):
        check_equal(a, b, f"sweep/{a.policy}: scan != vector")
    check(first.rows() == ref.rows(), "sweep: scan rows != vector rows")
    check(warm.rows() == ref.rows(), "sweep: warm scan rows != vector rows")
    print(f"sweep: {len(first.rows())} cells in tiles of up to "
          f"{scan_engine.BATCH_TILE}; first {t_first:.3f}s warm "
          f"{t_warm:.3f}s (vector reference {t_ref:.3f}s); rows and "
          f"per-job results bit-equal to vector, none delegated",
          flush=True)


def knn_phase(scenario) -> None:
    """The single-region scenario's paper-scale knowledge base (three
    learning weeks) queried with the eval week's oracle states: compiled
    Pallas ``knn_topk_batch`` and the jitted jax path against the numpy
    ``query_batch`` path."""
    import numpy as np

    from repro.core import oracle
    from repro.core.knowledge import states_from_schedule
    from repro.experiment import prepare_context
    from repro.experiment.scenario import WEEK
    from repro.kernels import knn

    check(knn.default_interpret() is False,
          "Pallas kernels would run in the interpreter on this device")
    mat = scenario.materialize()
    kb = prepare_context(mat, ("carbonflex-scale",)).kb
    t0 = mat.t0
    ev = [dataclasses.replace(j, arrival=j.arrival - t0)
          for j in mat.eval_jobs]
    sched = oracle.solve(ev, mat.ci.trace[t0:t0 + WEEK],
                         mat.cluster.capacity, horizon=WEEK)
    queries = states_from_schedule(ev, sched.schedule.alloc, mat.ci,
                                   len(mat.cluster.queues), t0=t0)
    k = kb.k
    kb.backend = "numpy"
    m_np, r_np, d_np = kb.query_batch(queries, k)
    # float32 tolerance on squared distances: the device rounds the
    # ||q||^2 + ||x||^2 - 2 q.x expansion in float32 over normalised
    # features bounded by |z| <= 3 times weights <= 2, so |d^2| stays below
    # a few hundred and its rounding error well below 1e-3.
    tol = 1e-3
    # neighbours whose numpy distance is separated from the ones beside
    # it by more than the tolerance must be the same case on every path
    d2 = d_np ** 2
    gap = np.full(d2.shape, np.inf)
    gap[:, 1:] = np.minimum(gap[:, 1:], d2[:, 1:] - d2[:, :-1])
    gap[:, :-1] = np.minimum(gap[:, :-1], d2[:, 1:] - d2[:, :-1])
    sep = gap > 2 * tol
    sep[:, -1] = False        # the (k+1)-th neighbour is not visible
    # "pallas": the compiled knn_topk_batch kernel; "jax": the jitted path
    # that backend="auto" picks on an accelerator
    kb.pallas_interpret = None
    for backend in ("pallas", "jax"):
        kb.backend = backend
        (m_k, r_k, d_k), t_first = _timed(lambda: kb.query_batch(queries, k))
        _, t_warm = _timed(lambda: kb.query_batch(queries, k))
        err = np.abs(d_k.astype(np.float64) ** 2 - d2)
        check(d_k.shape == d_np.shape == (len(queries), k),
              f"knn/{backend}: shapes {d_k.shape} vs {d_np.shape}")
        check(bool(np.isfinite(d_k).all()),
              f"knn/{backend}: non-finite distances")
        check(float(err.max()) <= tol, f"knn/{backend}: max |d^2 device - "
              f"d^2 numpy| = {err.max():.3g} > {tol}")
        check(bool((m_k[sep] == m_np[sep]).all()
                   and (r_k[sep] == r_np[sep]).all()),
              f"knn/{backend}: separated neighbours differ from numpy")
        print(f"knn/{backend}: kb {len(kb)} cases, {len(queries)} queries, "
              f"k={k}; first {t_first:.3f}s warm {t_warm:.3f}s; max |d^2 "
              f"err| {err.max():.3g} <= {tol}; {int(sep.sum())} separated "
              f"neighbours identical", flush=True)


def run_phases(capacity: int = CAPACITY) -> list[str]:
    """Run every phase, even after one fails, and return the names of the
    phases that failed (each failure is printed to stderr)."""
    from repro.core import scan_engine
    from repro.experiment import Scenario
    from repro.traces import DagConfig

    single = Scenario(region="south-australia", capacity=capacity,
                      learn_weeks=3, eval_weeks=1, seed=7, engine="scan")
    phases = {
        "single": lambda: run_phase(
            "single", single, ("carbon-agnostic", "wait-awhile",
                               "carbonflex-mpc", "carbonflex-scale")),
        "geo": lambda: run_phase(
            "geo", Scenario(regions=("south-australia", "california"),
                            capacity=capacity, learn_weeks=1, engine="scan"),
            ("geo-static", "geo-greedy", "geo-flex")),
        "dag": lambda: run_phase(
            "dag", Scenario(dag=DagConfig(), capacity=capacity,
                            learn_weeks=1, engine="scan"),
            ("dag-fcfs", "dag-carbon", "dag-cap")),
        "sweep": lambda: sweep_phase(capacity, scan_engine.BATCH_TILE),
        "knn": lambda: knn_phase(single),
    }
    failed = []
    for name, fn in phases.items():
        try:
            fn()
        except SmokeFailure as e:
            print(f"chip_smoke: {name} FAILED: {e}", file=sys.stderr)
            failed.append(name)
        except Exception:  # noqa: BLE001 - report it, run the next phase
            print(f"chip_smoke: {name} FAILED:", file=sys.stderr)
            traceback.print_exc()
            failed.append(name)
    return failed


def main() -> int:
    try:
        device = device_check()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    failed = run_phases()
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
