import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
cell on the production meshes and record memory/cost/collective analyses.

This proves the distribution config is coherent without real hardware:
sharding mismatches, OOM-at-compile and unsupported collectives all
surface here as hard failures.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                    # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --multi-pod        # 2x16x16
  PYTHONPATH=src python -m repro.launch.dryrun --out results/dryrun

Outputs one JSON per cell under --out (consumed by benchmarks/roofline.py
and by core/profiles.py for CarbonFlex scaling profiles).
"""
import argparse
import dataclasses
import json
import time
import traceback

import numpy as np

import jax

from repro.configs import ARCHS, LONG_CONTEXT_ARCHS
from repro.launch.hlo_analysis import analyze_module
from repro.launch.mesh import make_production_mesh
from repro.models import LogicalRules, ModelConfig, SHAPES
from repro.models.common import ShapeConfig
from repro.serve import abstract_cache, make_serve_step, serve_input_specs
from repro.train import OptimizerConfig, abstract_state, batch_specs, make_train_step

# v5e per-chip constants for the roofline terms (EXPERIMENTS.md §Roofline)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9


def runnable(arch: str, shape: ShapeConfig) -> bool:
    if shape.name == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
        return False          # full-attention archs skip (DESIGN.md §6)
    return True


def _adapted_cfg(cfg: ModelConfig, shape: ShapeConfig) -> ModelConfig:
    # bigger attention chunks for long prefill keep the scan shallow
    if shape.seq_len >= 32_768:
        return dataclasses.replace(cfg, attention_chunk=2048)
    return cfg


def input_specs(arch: str, shape_name: str, mesh=None):
    """ShapeDtypeStruct stand-ins (weak-type-correct, shardable, no device
    allocation) for every input of the (arch x shape) cell: the training
    batch for train shapes, (params, cache, tokens) templates for decode."""
    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    mesh = mesh or make_production_mesh()
    rules = LogicalRules(mesh)
    if shape.kind in ("train", "prefill"):
        return batch_specs(cfg, shape, rules)
    return {
        "tokens": serve_input_specs(cfg, shape.global_batch, rules),
        "cache": abstract_cache(cfg, shape.global_batch, shape.seq_len, rules),
    }


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh):
    """Returns (lowered, scan_trip_hints)."""
    rules = LogicalRules(mesh)
    cfg = _adapted_cfg(cfg, shape)
    hints = {"while": float(cfg.num_layers)}   # fallback for unnamed scans
    if shape.kind == "train":
        opt = OptimizerConfig(schedule="wsd" if cfg.lr_schedule == "wsd" else "cosine")
        step = make_train_step(cfg, rules, opt)
        state = abstract_state(cfg, rules)
        batch = batch_specs(cfg, shape, rules)
        lowered = jax.jit(step, donate_argnums=(0,)).lower(state, batch)
    else:
        from repro.models import api

        if shape.kind == "prefill":
            def prefill(params, batch):
                x, head = api.forward(params, batch["tokens"], cfg, rules,
                                      return_hidden=True,
                                      prefix_embeds=batch.get("prefix_embeds"))
                return (x[:, -1] @ head.astype(x.dtype))
            batch = batch_specs(cfg, shape, rules)
            params = api.abstract_params(cfg, rules)
            lowered = jax.jit(prefill).lower(params, batch)
        else:  # decode: one new token against a seq_len context
            step = make_serve_step(cfg, rules)
            params = api.abstract_params(cfg, rules)
            cache = abstract_cache(cfg, shape.global_batch, shape.seq_len, rules)
            toks = serve_input_specs(cfg, shape.global_batch, rules)
            lowered = jax.jit(step, donate_argnums=(1,)).lower(params, cache, toks)
    return lowered, hints


def analyze_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    lowered, hints = lower_cell(cfg, shape, mesh)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    stats = analyze_module(hlo, scan_trip_hints=hints)
    coll = stats.collectives

    # cost_analysis() counts while bodies once; the HLO walk re-weights by
    # trip counts (see hlo_analysis.ModuleStats), so prefer it.
    flops_per_dev = float(stats.flops)
    bytes_per_dev = float(stats.hbm_bytes)
    compute_s = flops_per_dev / PEAK_FLOPS
    memory_s = bytes_per_dev / HBM_BW
    collective_s = coll.wire_bytes / LINK_BW

    # MODEL_FLOPS: 6*N*D for train (fwd+bwd), 2*N*D for inference
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens
    else:
        tokens = shape.global_batch          # one new token per sequence
        model_flops = 2.0 * n_active * tokens
    model_flops_per_dev = model_flops / chips

    out = {
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.peak_memory_in_bytes,
        },
        "cost": {k: float(v) for k, v in cost.items()
                 if isinstance(v, (int, float)) and "{" not in k},
        "hlo_stats": {"flops": stats.flops, "hbm_bytes": stats.hbm_bytes},
        "collectives": coll.as_dict(),
        "roofline": {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": collective_s,
            "dominant": max(
                [("compute", compute_s), ("memory", memory_s),
                 ("collective", collective_s)], key=lambda kv: kv[1])[0],
            "model_flops_per_dev": model_flops_per_dev,
            "useful_flops_ratio": (model_flops_per_dev / flops_per_dev
                                   if flops_per_dev else None),
        },
        "params_total": cfg.param_count(),
        "params_active": n_active,
    }
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else sorted(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)

    failures = []
    for multi_pod in meshes:
        for arch in archs:
            for shape_name in shapes:
                if not runnable(arch, SHAPES[shape_name]):
                    print(f"SKIP {arch} x {shape_name} (full attention at 500k)")
                    continue
                tag = f"{arch}__{shape_name}__{'2x16x16' if multi_pod else '16x16'}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"CACHED {tag}")
                    continue
                try:
                    res = analyze_cell(arch, shape_name, multi_pod)
                    with open(path, "w") as f:
                        json.dump(res, f, indent=1)
                    r = res["roofline"]
                    peak = res["memory"]["peak_bytes"]
                    peak_str = f"{peak / 2**30:.2f} GiB/dev" \
                        if peak is not None else "n/a"
                    print(f"OK {tag}: compile {res['compile_s']}s "
                          f"peak {peak_str} "
                          f"compute {r['compute_s']*1e3:.1f}ms "
                          f"memory {r['memory_s']*1e3:.1f}ms "
                          f"coll {r['collective_s']*1e3:.1f}ms "
                          f"-> {r['dominant']}", flush=True)
                except Exception as e:  # noqa: BLE001 — record and continue
                    failures.append((tag, repr(e)))
                    print(f"FAIL {tag}: {e}", flush=True)
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, err in failures:
            print(" ", tag, err)
        raise SystemExit(1)
    print("\nall dry-run cells passed")


if __name__ == "__main__":
    main()
