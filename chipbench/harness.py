"""Run one benchmark cell once.

Every run: name the device (and stop with no result unless it is a TPU
with the chips the cell asks for), set up, send the cell's request once
to warm up its shapes, send it again in a closed loop with one client
for ``--seconds`` (the window closes at the end of the last request
that started inside it),
read the device's peak memory, and check every request's results
against the plain reference.  With
``--trace 1`` the window's requests run with the program's phase
profiler attached and one more request runs under ``jax.profiler``; the
per-layer metrics come from those.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by its name in ``BENCHMARK.json``:
``configs/<file>``, ``traffic/<name>.json``, ``metrics/<name>.py``.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import logging
import os
import shutil
import sys
import tempfile
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache", "jax")
REQUEST_SPAN = "chipbench.request"


class NoDevice(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


# --- files found by name -----------------------------------------------------


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_files(bench: dict, workload: str, root: str = ROOT,
               bench_dir: str = BENCH_DIR) -> tuple[dict, dict, dict]:
    """(cell, configuration data, traffic data) of a workload name."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"cells: {', '.join(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      cell["traffic"] + ".json"))
    return cell, config, traffic


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    """The ``read(ctx)`` of ``metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str) -> tuple[list[dict], list[dict]]:
    """(end-to-end, per-layer) metric entries this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (cell in m["workloads"] if "workloads" in m
               else m["moves"] in names)]
    return e2e, per


class MissingMetric(RuntimeError):
    """A per-layer metric listed for the cell found nothing to read."""


def read_per_layer(per: list[dict], ctx: dict, workload: str,
                   device_required: bool = True,
                   bench_dir: str = BENCH_DIR) -> dict:
    """The cell's per-layer metrics from a traced run's context.  A
    metric that ``BENCHMARK.json`` lists for the cell and that reads
    nothing is an error, so a renamed program or phase fails the run
    instead of dropping the metric; only a device-trace metric may read
    nothing where no device is required (a CPU test)."""
    out = {}
    for m in per:
        v = metric_reader(m["name"], bench_dir)(ctx)
        if v is None:
            if m["source"] == "device_trace" and not device_required:
                continue
            raise MissingMetric(f"{m['name']} found nothing to read in "
                                f"{workload}")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


# --- the device ---------------------------------------------------------------


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if require_tpu and (d.platform != "tpu" or len(devs) < chips):
        raise NoDevice(f"the cell needs {chips} TPU chip(s); JAX reports "
                       f"{len(devs)} x {d.platform} ({d.device_kind})")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def peak_bytes(chips: int) -> int | None:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def use_compile_cache() -> None:
    """JAX's persistent compilation cache, in a fixed directory of the
    checkout, for every compile however short.  The environment names
    the same directory, so program code that picks its cache from
    ``JAX_COMPILATION_CACHE_DIR`` takes this one."""
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# --- counters watched during the window ---------------------------------------


class _DelegationLog(logging.Handler):
    """Counts the cases the scan engine reports it handed to the host
    vector engine (``scan batch: N case(s) delegated ...``)."""

    def __init__(self) -> None:
        super().__init__(logging.INFO)
        self.cases = 0

    def emit(self, record: logging.LogRecord) -> None:
        if "delegated" in record.msg and record.args:
            self.cases += int(record.args[0])


class Watch:
    """Counts compilations (JAX's compile events) and delegated cases
    while active."""

    def __init__(self) -> None:
        self.compiles = 0
        self.delegation = _DelegationLog()
        self._on = False

    def _listen(self, event: str, duration: float, **kw) -> None:
        if self._on and event.startswith("/jax/core/compile/"):
            self.compiles += 1

    @contextlib.contextmanager
    def active(self):
        import jax

        log = logging.getLogger("repro.core.scan_engine")
        old_level = log.level
        log.setLevel(logging.INFO)
        log.addHandler(self.delegation)
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        self._on = True
        try:
            yield self
        finally:
            self._on = False
            jax.monitoring.unregister_event_duration_listener(self._listen)
            log.removeHandler(self.delegation)
            log.setLevel(old_level)


# --- one run -------------------------------------------------------------------


def _annotating_profiler():
    """A phase profiler that also opens a trace annotation
    ``chipbench.<phase>`` around each phase the program brackets with
    ``phase()`` (provision, learn), so the trace's idle time is labelled
    by what the host was doing."""
    import jax
    from repro.telemetry import PhaseProfiler

    class AnnotatingProfiler(PhaseProfiler):
        @contextlib.contextmanager
        def phase(self, name: str, sync=None):
            with jax.profiler.TraceAnnotation(f"chipbench.{name}"), \
                    super().phase(name, sync):
                yield

    return AnnotatingProfiler()


def _traced(requests, tmp: str) -> tuple[dict, dict | None]:
    """One request under ``jax.profiler`` (no Python tracer); returns it
    and the trace's reduction over the request's span."""
    import jax
    from repro.telemetry import Telemetry

    from . import trace as trace_mod

    tele = Telemetry(profiler=_annotating_profiler())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    with jax.profiler.trace(tmp, profiler_options=opts):
        with jax.profiler.TraceAnnotation(REQUEST_SPAN):
            out = requests.send(telemetry=tele)
    files = [os.path.join(d, f) for d, _, fs in os.walk(tmp) for f in fs
             if f.endswith(".xplane.pb")]
    if not files:
        return out, None
    return out, trace_mod.reduce(trace_mod.extract(files[0]), REQUEST_SPAN)


def measure(workload: str, seed: int, seconds: float, traced: bool, *,
            t_start: float, require_tpu: bool = True, bench: dict | None = None,
            files=None, log=None) -> dict:
    """One run of ``workload``; returns the result line's object.
    ``files`` overrides the (cell, config, traffic) that ``BENCHMARK.json``
    names; tests use it to run a cell at a small size."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    bench = bench or load_benchmark()
    cell, config, traffic = files or cell_files(bench, workload)
    device = device_info(cell["chips"], require_tpu=require_tpu)
    if require_tpu:
        use_compile_cache()
    if os.path.join(ROOT, "src") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "src"))

    from repro.core import scan_engine
    from repro.telemetry import PhaseProfiler, Telemetry

    from . import compare, generator
    from .reference import Reference

    plan = generator.make_plan(config, traffic, seed)
    requests = generator.Requests(plan)
    log(f"{workload}: {plan.call} of {len(plan.policies)} policies = "
        f"{plan.units_per_request} {plan.unit} per request over "
        f"{len(plan.worlds)} world(s); "
        f"device {device}")

    watch = Watch()
    outs = []
    # the proof that a vmapped tile ran: its program enters the in-memory
    # jit cache during warm-up (empty in a fresh process; emptied here for
    # a process that ran cells before)
    batched = scan_engine._single_chunk_batch
    batched.clear_cache()
    with watch.active():
        outs.append(requests.send())                      # warm-up
        tiles = batched._cache_size()
        setup_s = time.perf_counter() - t_start
        compiles_setup = watch.compiles
        watch.compiles = 0
        prof = PhaseProfiler() if traced else None
        tele = Telemetry(profiler=prof) if traced else None
        w0 = time.perf_counter()
        n = 0
        while n == 0 or time.perf_counter() - w0 < seconds:
            outs.append(requests.send(telemetry=tele))
            n += 1
        w1 = time.perf_counter()
        window_compiles = watch.compiles
        device_red = None
        if traced:
            tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
            try:
                out, device_red = _traced(requests, tmp)
                outs.append(out)
            finally:
                shutil.rmtree(tmp, ignore_errors=True)
    window = w1 - w0
    units = n * plan.units_per_request
    device["memory_peak_bytes"] = peak_bytes(cell["chips"])
    log(f"set-up {setup_s:.3f} s ({compiles_setup} compile events); window "
        f"{window:.3f} s, {n} requests, {units} {plan.unit}; "
        f"peak {device['memory_peak_bytes']} bytes")

    # the check: every request's compared results against the reference
    keep = [compare.digest(o, plan) for o in outs]
    del outs
    t_ref = time.perf_counter()
    ref = Reference(plan)
    total = compare.Tally()
    failed = 0
    for k, d in enumerate(keep):
        tally = compare.check_request(d, plan, ref, f"request {k}")
        failed += 0 if tally.passed() else 1
        total.merge(tally)
    total.count("window_compiles", window_compiles)
    total.count("delegated_cases", watch.delegation.cases)
    if plan.call == "sweep":
        total.count("tile_programs_missing", 0 if tiles > 0 else 1)
    log(f"reference check {time.perf_counter() - t_ref:.3f} s over "
        f"{len(keep)} requests x {plan.n_compare} results, "
        f"{len(ref.computed)} distinct")
    for note in total.notes:
        log(f"mismatch: {note}")

    bench_e2e, bench_per = cell_metrics(bench, workload)
    metrics = {}
    if not traced:
        for m in bench_e2e:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": m["unit"]}
            elif m["name"] == traffic["rate_metric"]:
                metrics[m["name"]] = {"value": units / window,
                                      "unit": m["unit"]}
    else:
        ctx = {"call": plan.call, "units": units, "window_s": window,
               "phases": dict(prof.seconds), "device": device_red,
               "traced_units": plan.units_per_request}
        metrics = read_per_layer(bench_per, ctx, workload,
                                 device_required=require_tpu)
        if device_red is not None:
            device["busy_s"] = device_red["busy_s"]
            device["window_s"] = device_red["window_s"]
    result = {"correct": total.passed(), "attempted": len(keep),
              "failed": failed, "metrics": metrics, "device": device}
    if traced and device_red is not None:
        result["breakdown"] = {"device_ops": device_red["device_ops"],
                               "idle_gaps": device_red["idle_gaps"]}
    result["compared"] = total.as_json()
    for line in total.lines():
        log(line)
    return result
