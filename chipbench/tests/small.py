"""A benchmark cell cut to a size a CPU test can hold: 20 servers (20 a
region in a geo cluster), at most two worlds a request or a run's
cycle and two regions a sweep, three noisy forecasts, no job-count
band."""
import copy

from chipbench import harness


def small_files(workload: str, capacity: int = 20):
    bench = harness.load_benchmark()
    cell, config, traffic = harness.cell_files(bench, workload)
    config = copy.deepcopy(config)
    config["scenario"]["capacity"] = capacity * len(
        config["scenario"].get("regions", [None]))
    config.pop("eval_jobs_band", None)
    traffic = copy.deepcopy(traffic)
    if "seeds_per_request" in traffic:
        traffic["seeds_per_request"] = min(2, traffic["seeds_per_request"])
    if "worlds_per_run" in traffic:
        traffic["worlds_per_run"] = min(2, traffic["worlds_per_run"])
    if "sweep_regions" in traffic:
        traffic["sweep_regions"] = traffic["sweep_regions"][:2]
    if "forecasts" in traffic:
        traffic["forecasts"]["seeds"] = 1
        traffic["forecasts"]["sigmas"] = traffic["forecasts"]["sigmas"][:3]
    return bench, (cell, config, traffic)
