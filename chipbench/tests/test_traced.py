"""A ``--trace 1`` run reports the phase metrics of its cell; on the CPU
the trace holds no TPU plane, so the device metrics are left out."""
import time

import pytest

from chipbench import harness
from chipbench.tests.small import small_files


@pytest.mark.parametrize("workload,expect", [
    ("single.whatif", {"provision_ms.run", "learn_ms.run", "decide_ms.run",
                       "execute_ms.run", "unprofiled_ms.run"}),
    ("single.sweep-forecast", {"provision_ms.sweep", "decide_ms.sweep",
                               "execute_ms.sweep", "unprofiled_ms.sweep"}),
])
def test_traced_run_reports_phase_metrics(workload, expect):
    bench, files = small_files(workload)
    r = harness.measure(workload, 99, 0.5, True, t_start=time.perf_counter(),
                        require_tpu=False, bench=bench, files=files,
                        log=lambda m: None)
    assert r["correct"] is True
    assert set(r["metrics"]) == expect
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert "busy_s" not in r["device"] and "breakdown" not in r


def _ctx(device):
    return {"call": "sweep", "units": 64, "window_s": 1.0, "traced_units": 64,
            "phases": {"provision": 0.25, "decide": 0.25, "execute": 0.25},
            "device": device}


def test_listed_metric_that_reads_nothing_fails_the_run():
    """A trace whose scan programs were renamed drops no metric silently."""
    bench = harness.load_benchmark()
    _, per = harness.cell_metrics(bench, "single.sweep-regions")
    renamed = {"program_s": {"jit_renamed_chunk": 0.01}, "busy_s": 0.01,
               "window_s": 1.0}
    with pytest.raises(harness.MissingMetric, match="scan_device_ms.sweep"):
        harness.read_per_layer(per, _ctx(renamed), "single.sweep-regions")
    got = harness.read_per_layer(per, _ctx(None), "single.sweep-regions",
                                 device_required=False)
    assert "scan_device_ms.sweep" not in got and "decide_ms.sweep" in got
    with pytest.raises(harness.MissingMetric, match="found nothing to read"):
        harness.read_per_layer(per, _ctx(None), "single.sweep-regions")
