"""Compiles the device path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what the Pallas interpreter and XLA:CPU accept
(unsupported gathers, unaligned tiles, programs that do not fit), so these
tests compile the main path's kernels and jitted scan chunks at the
paper's scale (capacity 150) for one v5e chip:

- the knowledge base's KNN kernels (``knn_topk``/``knn_topk_batch``,
  ``interpret=False``) at the three-learning-week case-base shape, each
  lowered to a Mosaic ``tpu_custom_call``;
- ``_single_chunk``, ``_geo_chunk`` and one ``BATCH_TILE``-wide
  ``_single_chunk_batch`` under x64, with the shapes of the programs
  ``_build_single``/``_build_geo`` build on the CPU for the scan-native
  policy families.

The topology is described inside a fixture, so collection never loads
the TPU library; the persistent compilation cache is off around these
compiles (an entry written for a described chip cannot be read back).
"""
import functools
import os

import numpy as np
import pytest

import jax
from jax.sharding import SingleDeviceSharding

from repro.core import scan_engine
from repro.core.simulator import _packed_for, _policy_ci_view
from repro.experiment import Scenario, prepare_context
from repro.experiment.registry import make_policy
from repro.experiment.scenario import WEEK
from repro.kernels import knn
from repro.traces import DagConfig

CAPACITY = 150
KB_CASES = 3 * WEEK     # three learning weeks, one case per slot


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU library would otherwise write its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _specs(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


def _feature_dim() -> int:
    nq = len(Scenario(capacity=CAPACITY).queues())
    return 7 + 2 * nq          # knowledge.build_state's Table-2 layout


@pytest.mark.parametrize("batch", [False, True], ids=["single", "batch"])
def test_knn_kernel_compiles_to_mosaic(one_chip, batch):
    d = _feature_dim()
    cases = jax.ShapeDtypeStruct((KB_CASES, d), np.float32, sharding=one_chip)
    if batch:
        q = jax.ShapeDtypeStruct((WEEK, d), np.float32, sharding=one_chip)
        fn = functools.partial(knn.knn_topk_batch, k=5, interpret=False)
    else:
        q = jax.ShapeDtypeStruct((d,), np.float32, sharding=one_chip)
        fn = functools.partial(knn.knn_topk, k=5, interpret=False)
    compiled = jax.jit(fn).lower(cases, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def worlds():
    """Capacity-150 worlds (built once; materialization is host-only)."""
    return {
        "single": Scenario(region="south-australia", capacity=CAPACITY,
                           learn_weeks=1, seed=7),
        "dag": Scenario(dag=DagConfig(), capacity=CAPACITY, learn_weeks=1),
        "geo": Scenario(regions=("south-australia", "california"),
                        capacity=CAPACITY, learn_weeks=1),
    }


def _case(scenario, policy_name):
    mat = scenario.materialize()
    ctx = prepare_context(mat, (policy_name,))
    policy = make_policy(policy_name, ctx)
    cluster = mat.geo if mat.is_geo else mat.cluster
    ci = mat.mci if mat.is_geo else mat.ci
    packed = _packed_for(mat.eval_jobs)
    kind = scan_engine.native_kind(policy, cluster, None)
    assert kind is not None, policy_name
    ci_pol = _policy_ci_view(ci)
    policy.on_window_start(ci_pol, mat.t0, WEEK, packed.jobs, cluster)
    return packed, cluster, policy, ci_pol, kind, mat.t0


@pytest.mark.parametrize("world,policy_name", [
    ("single", "carbon-agnostic"), ("single", "wait-awhile"),
    ("single", "carbonflex-mpc"), ("single", "carbonflex-scale"),
    ("dag", "dag-carbon"), ("dag", "dag-cap"),
])
def test_single_chunk_compiles(one_chip, worlds, world, policy_name):
    with jax.enable_x64(True):
        packed, cluster, policy, ci_pol, kind, t0 = _case(worlds[world],
                                                          policy_name)
        prog = scan_engine._build_single(packed, cluster, policy, ci_pol,
                                         kind, t0, WEEK)
        if world == "dag":
            assert prog.deps != "none"
        xs = prog.xs_fn(np.arange(t0, t0 + scan_engine.CHUNK))
        lowered = scan_engine._single_chunk.lower(
            _specs(prog.consts, one_chip), _specs(prog.carry0, one_chip),
            _specs(xs, one_chip), kind=prog.kind, uniform=prog.uniform,
            deps=prog.deps)
        compiled = lowered.compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("policy_name", ["geo-static", "geo-greedy",
                                         "geo-flex"])
def test_geo_chunk_compiles(one_chip, worlds, policy_name):
    with jax.enable_x64(True):
        packed, geo, policy, ci_pol, kind, t0 = _case(worlds["geo"],
                                                      policy_name)
        prog = scan_engine._build_geo(packed, geo, policy, ci_pol, t0, WEEK,
                                      kind)
        xs = prog.xs_fn(np.arange(t0, t0 + scan_engine.CHUNK))
        lowered = scan_engine._geo_chunk.lower(
            _specs(prog.consts, one_chip), _specs(prog.carry0, one_chip),
            _specs(xs, one_chip), kind=kind,
            lookahead=int(getattr(policy, "lookahead", 24)),
            uniform=prog.uniform)
        compiled = lowered.compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("policy_name", ["carbon-agnostic", "wait-awhile"])
def test_single_chunk_batch_compiles(one_chip, worlds, policy_name):
    """One vmapped tile of ``BATCH_TILE`` structurally identical cells."""
    tile = scan_engine.BATCH_TILE

    def tiled(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((tile,) + np.shape(a),
                                           np.asarray(a).dtype,
                                           sharding=one_chip), tree)

    with jax.enable_x64(True):
        packed, cluster, policy, ci_pol, kind, t0 = _case(worlds["single"],
                                                          policy_name)
        prog = scan_engine._build_single(packed, cluster, policy, ci_pol,
                                         kind, t0, WEEK)
        xs = prog.xs_fn(np.arange(t0, t0 + scan_engine.CHUNK))
        lowered = scan_engine._single_chunk_batch.lower(
            tiled(prog.consts), tiled(prog.carry0), tiled(xs),
            kind=prog.kind, uniform=prog.uniform, deps=prog.deps)
        compiled = lowered.compile()
    assert compiled.memory_analysis() is not None
