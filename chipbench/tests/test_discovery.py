"""A configuration, a traffic mix and a per-layer metric dropped into a
copy of the benchmark's directories are found by name, with no edit to
any file that was there."""
import json
import os
import shutil

from chipbench import generator, harness


def test_new_files_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH_DIR, root / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    bench_dir = str(root / "chipbench")
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file()}

    config = json.loads((root / "chipbench/configs/paper-single-150.json")
                        .read_text())
    config["scenario"]["region"] = "germany"
    (root / "chipbench/configs/germany-150.json").write_text(
        json.dumps(config))
    (root / "chipbench/traffic/whatif-mpc.json").write_text(json.dumps(
        {"call": "run", "policies": ["carbon-agnostic", "carbonflex-mpc"],
         "rate_metric": "policy_weeks_per_s"}))
    (root / "chipbench/metrics/requests_seen.run.py").write_text(
        "def read(ctx):\n    return float(ctx['units'])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "germany-150", "source": "x",
                             "file": "chipbench/configs/germany-150.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "germany.mpc", "config": "germany-150",
                               "traffic": "whatif-mpc", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] == "policy_weeks_per_s":
            m["workloads"].append("germany.mpc")
    bench["per_layer"].append({"name": "requests_seen.run", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "policy_weeks_per_s",
                               "workloads": ["germany.mpc"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    bench = harness.load_benchmark(str(root))
    cell, config, traffic = harness.cell_files(bench, "germany.mpc",
                                               root=str(root),
                                               bench_dir=bench_dir)
    assert config["scenario"]["region"] == "germany"
    e2e, per = harness.cell_metrics(bench, "germany.mpc")
    assert [m["name"] for m in e2e] == ["setup_s", "policy_weeks_per_s"]
    assert [m["name"] for m in per] == ["requests_seen.run"]
    assert harness.metric_reader("requests_seen.run", bench_dir)(
        {"units": 3}) == 3.0
    plan = generator.make_plan(config, traffic, 12345)
    assert plan.policies == ("carbon-agnostic", "carbonflex-mpc")
    assert plan.worlds[0].regions == ("germany",)
    assert all(p.read_bytes() == b for p, b in before.items())
