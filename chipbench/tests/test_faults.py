"""A run whose timed path is broken underneath reports ``correct``
false: once for each fault a cell of this benchmark can have, and in the
DAG cell a task released a slot early.  The runs skip the look for a
chip and run a small cell on the CPU.  No cell runs across chips, so
there is no exchange between chips to leave out."""
import time

import numpy as np
import pytest

from chipbench import harness
from chipbench.tests.small import small_files

CELLS = [c["name"] for c in harness.load_benchmark()["workloads"]]


def _run(workload: str) -> dict:
    bench, files = small_files(workload)
    return harness.measure(workload, 2 ** 31 + 5, 0.5, False,
                           t_start=time.perf_counter(), require_tpu=False,
                           bench=bench, files=files, log=lambda m: None)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    assert _run(workload)["correct"] is True


@pytest.mark.parametrize("workload", CELLS)
def test_state_left_unchanged(monkeypatch, workload):
    """Each device chunk hands back the state it was given."""
    from repro.core import scan_engine

    for name in ("_single_chunk", "_single_chunk_batch", "_geo_chunk"):
        real = getattr(scan_engine, name)

        def stuck(consts, carry, xs, *a, _real=real, **kw):
            _, ys = _real(consts, carry, xs, *a, **kw)
            return carry, ys

        stuck._cache_size = real._cache_size
        stuck.clear_cache = real.clear_cache
        monkeypatch.setattr(scan_engine, name, stuck)
    assert _run(workload)["correct"] is False


@pytest.mark.parametrize("workload", CELLS)
def test_half_the_batch_left_out(monkeypatch, workload):
    """Only the first half of a batch's cases run; the rest repeat them."""
    from repro.core import scan_engine

    real = scan_engine.simulate_many_scan

    def half(cases):
        cases = list(cases)
        done = real(cases[:max(1, len(cases) // 2)])
        return [done[i % len(done)] for i in range(len(cases))]

    monkeypatch.setattr(scan_engine, "simulate_many_scan", half)
    assert _run(workload)["correct"] is False


@pytest.mark.parametrize("workload", CELLS)
def test_answer_altered_where_produced(monkeypatch, workload):
    """Where a result is produced (the host replay of a single-region or
    a geo case), one slot's energy moves by a part in a million."""
    from repro.core import scan_engine

    for name in ("_account_single", "_run_geo_native"):
        real = getattr(scan_engine, name)

        def altered(*a, _real=real, **kw):
            res = _real(*a, **kw)
            s = max(res.slots, key=lambda x: x.energy_kwh)
            s.energy_kwh = float(np.nextafter(s.energy_kwh * (1 + 1e-6),
                                              1e300))
            return res

        monkeypatch.setattr(scan_engine, name, altered)
    assert _run(workload)["correct"] is False


def test_release_in_the_slot_its_last_predecessor_ends(monkeypatch):
    """In the DAG cell a task whose last predecessor completes in slot t
    is released in t, not t + 1: it may run beside its predecessor, and
    its slack and deadline count from t."""
    import jax

    from repro.core import scan_engine

    real = scan_engine._single_step

    def early(consts, carry, x, *, kind, uniform, deps):
        kw = dict(kind=kind, uniform=uniform, deps=deps)
        if deps == "none":
            return real(consts, carry, x, **kw)
        now = real(consts, carry, x, **kw)[0]["pending"]
        out, ys = real(consts, dict(carry, pending=carry["pending"] | now),
                       x, **kw)
        return dict(out, pending=out["pending"] & ~now), ys

    def fresh(fun):
        # a new function, so JAX traces the broken step afresh
        def chunk(consts, carry, xs, kind, uniform, deps):
            return fun(consts, carry, xs, kind, uniform, deps)

        return jax.jit(chunk, static_argnames=("kind", "uniform", "deps"))

    monkeypatch.setattr(scan_engine, "_single_step", early)
    for name in ("_single_chunk", "_single_chunk_batch"):
        monkeypatch.setattr(scan_engine, name,
                            fresh(getattr(scan_engine, name).__wrapped__))
    assert _run("dag.whatif")["correct"] is False
