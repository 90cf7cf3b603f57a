"""Elastic scaling profiles (paper §2.3 / §3).

Two sources of profiles:

1. Parametric families (Amdahl-style) mirroring the paper's Table 3
   High/Moderate/Low scalability classes — used by unit tests and the
   cluster simulator when no compiled artifact is available.

2. Roofline-derived profiles (DESIGN.md §7): given the compiled step's
   per-slice FLOPs, HBM bytes and DP-collective bytes (from the dry-run's
   ``cost_analysis`` + HLO collective scan), derive step time at DP degree k

       tau(k) = max(compute / k, memory / k, collective(k))

   with ring-all-reduce collective time ~ 2*(k-1)/k * grad_bytes / link_bw
   (flat-ish in k), then normalised marginal throughput

       T(k) = tau(1) / tau(k) * k        (work per unit time, k chunks)
       p(k) = T(k) - T(k-1),  p(k_min) = 1 by construction.

This replaces the paper's one-time wall-clock profiling (§6.1) — the
analytic profile has the same monotone-decreasing shape and plays the same
role in Algorithms 1–3.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# --- parametric profiles -------------------------------------------------

# Mirrors Table 3 scalability classes. Values chosen so that the mean
# marginal throughput (elasticity) is ~0.95 / ~0.75 / ~0.45.
_CLASS_SIGMA = {"high": 0.05, "moderate": 0.35, "low": 0.9}


def amdahl_profile(k_min: int, k_max: int, sigma: float) -> np.ndarray:
    """Marginal-throughput profile from an Amdahl-like throughput curve.

    Throughput at scale k: T(k) = k / (1 + sigma * (k - 1)).  sigma = 0 is
    linear scaling; larger sigma = more communication per unit compute.
    Returns marginals p[i] = T(k_min+i) - T(k_min+i-1), normalised so
    p(k_min) = 1 (paper §3 requires p_j(k_min) = 1).
    """
    ks = np.arange(k_min - 1, k_max + 1, dtype=np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        t = np.where(ks > 0, ks / (1.0 + sigma * (ks - 1.0)), 0.0)
    marg = np.diff(t)
    base = marg[0]
    if base <= 0:
        raise ValueError("degenerate profile")
    # Negative marginals (sigma > 1: adding servers would *hurt*) clamp to
    # zero — a rational scheduler simply never allocates past the peak.
    p = np.maximum(marg / base, 0.0)
    # Guard strict monotone decrease (Theorem 4.1 condition 1).
    p = np.minimum.accumulate(p)
    return p


def class_profile(scalability: str, k_min: int = 1, k_max: int = 16) -> np.ndarray:
    return amdahl_profile(k_min, k_max, _CLASS_SIGMA[scalability])


@dataclasses.dataclass(frozen=True)
class WorkloadSpec:
    """One entry of the paper's Table 3: a profiled elastic workload."""

    name: str
    impl: str                  # "MPI" | "Pytorch" | "JAX"
    comm_size_mb: float
    scalability: str           # "high" | "moderate" | "low"
    power_kw: float = 1.0      # per-server draw (GPU cluster: heterogeneous)


# The paper's Table 3 workload mix (names + comm sizes + classes).  Power
# numbers for the GPU cluster follow the paper's observation that highly
# scalable (compute-dense) workloads draw more power.
TABLE3_WORKLOADS: tuple[WorkloadSpec, ...] = (
    WorkloadSpec("nbody-100k", "MPI", 5.3, "high", 1.00),
    WorkloadSpec("nbody-50k", "MPI", 0.53, "high", 1.00),
    WorkloadSpec("nbody-2k", "MPI", 0.16, "moderate", 0.85),
    WorkloadSpec("jacobi-10k", "MPI", 0.1, "moderate", 0.85),
    WorkloadSpec("jacobi-1k", "MPI", 51.2, "low", 0.70),
    WorkloadSpec("lammps", "MPI", 28.6, "low", 0.70),
    WorkloadSpec("gromacs", "MPI", 7.16, "low", 0.70),
    WorkloadSpec("vgg16", "Pytorch", 233.1, "low", 0.70),
    WorkloadSpec("resnet18", "Pytorch", 44.7, "low", 0.72),
    WorkloadSpec("resnet50", "Pytorch", 97.8, "moderate", 0.85),
    WorkloadSpec("efficientnetv2-s", "Pytorch", 170.5, "high", 1.00),
    WorkloadSpec("effnet-s", "Pytorch", 82.7, "high", 1.00),
    WorkloadSpec("vit-b32", "Pytorch", 336.6, "moderate", 0.85),
)


# --- roofline-derived profiles -------------------------------------------


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    """Per-slice compiled-step roofline inputs (seconds are derived)."""

    flops: float                 # HLO FLOPs per step per slice
    hbm_bytes: float             # HLO bytes accessed per step per slice
    grad_bytes: float            # DP all-reduce payload per step (model grads)
    peak_flops: float = 197e12   # TPU v5e bf16
    hbm_bw: float = 819e9        # bytes/s
    link_bw: float = 50e9        # ICI bytes/s/link

    def step_time(self, k: int) -> float:
        """Roofline step time when the job's work is split over k slices."""
        compute = self.flops / k / self.peak_flops
        memory = self.hbm_bytes / k / self.hbm_bw
        if k == 1:
            coll = 0.0
        else:
            coll = 2.0 * (k - 1) / k * self.grad_bytes / self.link_bw
        return max(compute, memory) + coll


def roofline_profile(terms: RooflineTerms, k_min: int = 1, k_max: int = 16) -> np.ndarray:
    """Marginal-throughput profile from compiled roofline terms.

    Strong scaling of a fixed per-step workload: throughput at k slices is
    T(k) = tau(k_min) / tau(k) (normalised so T(k_min) = 1 slice-unit of
    work rate times k_min...); marginals are the discrete derivative,
    normalised to p(k_min) = 1 per the paper's §3 convention."""
    ks = np.arange(k_min - 1, k_max + 1)
    t = np.zeros(len(ks))
    base = terms.step_time(max(k_min, 1))
    for i, k in enumerate(ks):
        t[i] = 0.0 if k <= 0 else base / terms.step_time(int(k)) * max(k_min, 1)
    marg = np.diff(t)
    base_m = marg[0]
    if base_m <= 0:
        raise ValueError("degenerate roofline profile")
    p = np.maximum(marg / base_m, 0.0)
    return np.minimum.accumulate(p)


def elasticity_of(profile: np.ndarray) -> float:
    return float(np.mean(profile))


def terms_from_dryrun(arch: str, dryrun_dir: str = "results/dryrun_opt",
                      shape: str = "train_4k", mesh: str = "16x16",
                      tokens_per_step: int = 65_536) -> RooflineTerms:
    """Build RooflineTerms for an architecture from its compiled dry-run
    cell (closes the loop: the scheduling layer's scaling profiles come
    from the same artifacts as EXPERIMENTS.md §Roofline).

    Unit convention (per CHIP, job on k fixed-size DP slices of
    ``slice_chips`` chips): the cell was measured with the job spread over
    ``chips/slice_chips`` slices, so per-chip compute at k=1 is the cell's
    per-device flops scaled back up; the DP all-reduce payload per chip is
    the slice's shard of the gradients (2 bytes x active params /
    slice_chips), with the ring factor applied inside
    ``RooflineTerms.step_time``."""
    import json
    import os

    slice_chips = 16
    path = os.path.join(dryrun_dir, f"{arch}__{shape}__{mesh}.json")
    with open(path) as f:
        d = json.load(f)
    slices_measured = max(d["chips"] // slice_chips, 1)
    # The cell was measured at train_4k's 1M-token global batch; a cluster
    # job's per-step batch (tokens_per_step) scales the compute/memory
    # terms while the gradient-sync payload stays fixed — this is what
    # produces the monotone-decreasing marginal-throughput curve and why
    # bigger models (more compute per sync byte) are MORE elastic, the
    # paper's §2.3 compute-to-communication observation.
    cell_tokens = 256 * 4096 if shape == "train_4k" else 32 * 32_768
    scale = tokens_per_step / cell_tokens
    return RooflineTerms(
        flops=float(d["hlo_stats"]["flops"]) * slices_measured * scale,
        hbm_bytes=float(d["hlo_stats"]["hbm_bytes"]) * slices_measured * scale,
        grad_bytes=2.0 * float(d["params_active"]) / slice_chips,
    )


def profile_from_dryrun(arch: str, k_min: int = 1, k_max: int = 16,
                        **kw) -> np.ndarray:
    return roofline_profile(terms_from_dryrun(arch, **kw), k_min, k_max)
