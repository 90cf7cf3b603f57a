"""Pallas TPU kernels: tiled squared-Euclidean distances for the KNN
knowledge-base lookup (paper §4.3 / Algorithm 2).

Two entry points:

- ``knn_topk``        — single query against the (N, D) case base.  The
  kernel tiles the case base over N into VMEM blocks and computes the
  fused (x - q)^2 row reduction per block (one pass, no (N, D) temporary
  in HBM).
- ``knn_topk_batch``  — Q queries at once.  The kernel tiles a (Q, N)
  distance matrix into (BLOCK_Q, BLOCK_N) VMEM blocks and uses the MXU
  via the ``||q||^2 + ||x||^2 - 2 q.x`` expansion (one ``jnp.dot`` per
  block), which is the right shape for year-scale sweeps that match many
  slots / many runs per dispatch.

Top-k over the resulting distances runs through ``lax.top_k`` in the jit
wrapper — top-k over a few thousand scalars is not worth a custom kernel.

``interpret`` resolution: ``None`` (the default) auto-detects the backend
— the kernels compile to Mosaic on TPU and fall back to the Pallas
interpreter on every other backend.  Callers can
force either mode explicitly (``KnowledgeBase(pallas_interpret=...)``
plumbs through to here).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_N = 256
BLOCK_Q = 128
# pad feature dim to the lane width so the VMEM tile is hardware-aligned
LANE = 128


@functools.cache
def default_interpret() -> bool:
    """Interpret everywhere except a real TPU backend."""
    return jax.default_backend() != "tpu"


def _resolve_interpret(interpret: bool | None) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def _dist_kernel(cases_ref, query_ref, out_ref):
    x = cases_ref[...].astype(jnp.float32)          # (BLOCK_N, Dp)
    q = query_ref[...].astype(jnp.float32)          # (1, Dp)
    diff = x - q
    out_ref[...] = jnp.sum(diff * diff, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _squared_distances(cases: jax.Array, query: jax.Array,
                       interpret: bool) -> jax.Array:
    n, d = cases.shape
    dp = ((d + LANE - 1) // LANE) * LANE
    np_ = ((n + BLOCK_N - 1) // BLOCK_N) * BLOCK_N
    cases_p = jnp.zeros((np_, dp), cases.dtype).at[:n, :d].set(cases)
    query_p = jnp.zeros((1, dp), query.dtype).at[0, :d].set(query)
    out = pl.pallas_call(
        _dist_kernel,
        grid=(np_ // BLOCK_N,),
        in_specs=[
            pl.BlockSpec((BLOCK_N, dp), lambda i: (i, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_N, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        interpret=interpret,
    )(cases_p, query_p)
    return out[:n, 0]


def squared_distances(cases: jax.Array, query: jax.Array,
                      interpret: bool | None = None) -> jax.Array:
    """(N, D), (D,) -> (N,) squared Euclidean distances."""
    return _squared_distances(cases, query, _resolve_interpret(interpret))


def knn_topk(cases: jax.Array, query: jax.Array, k: int,
             interpret: bool | None = None):
    """Top-k nearest cases: returns (distances, indices) ascending."""
    d2 = squared_distances(cases, query, interpret=interpret)
    neg, idx = jax.lax.top_k(-d2, k)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), idx


# --- batched multi-query path ---------------------------------------------


def _dist_kernel_batch(queries_ref, cases_ref, out_ref):
    q = queries_ref[...].astype(jnp.float32)        # (BLOCK_Q, Dp)
    x = cases_ref[...].astype(jnp.float32)          # (BLOCK_N, Dp)
    qn = jnp.sum(q * q, axis=1, keepdims=True)      # (BLOCK_Q, 1)
    xn = jnp.sum(x * x, axis=1, keepdims=True)      # (BLOCK_N, 1)
    # MXU block: -2 q.x^T, then the rank-1 norm corrections on the VPU.
    # HIGHEST: the TPU's default f32 matmul is one bf16 pass, whose error
    # (~0.1 in d^2 on Table-2 features) reorders near neighbours.
    cross = jnp.dot(q, x.T, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
    out_ref[...] = qn + xn.T - 2.0 * cross


@functools.partial(jax.jit, static_argnames=("interpret",))
def _squared_distances_batch(cases: jax.Array, queries: jax.Array,
                             interpret: bool) -> jax.Array:
    n, d = cases.shape
    qn, _ = queries.shape
    dp = ((d + LANE - 1) // LANE) * LANE
    np_ = ((n + BLOCK_N - 1) // BLOCK_N) * BLOCK_N
    qp = ((qn + BLOCK_Q - 1) // BLOCK_Q) * BLOCK_Q
    cases_p = jnp.zeros((np_, dp), cases.dtype).at[:n, :d].set(cases)
    queries_p = jnp.zeros((qp, dp), queries.dtype).at[:qn, :d].set(queries)
    out = pl.pallas_call(
        _dist_kernel_batch,
        grid=(qp // BLOCK_Q, np_ // BLOCK_N),
        in_specs=[
            pl.BlockSpec((BLOCK_Q, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((BLOCK_N, dp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_Q, BLOCK_N), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp, np_), jnp.float32),
        interpret=interpret,
    )(queries_p, cases_p)
    return out[:qn, :n]


def squared_distances_batch(cases: jax.Array, queries: jax.Array,
                            interpret: bool | None = None) -> jax.Array:
    """(N, D), (Q, D) -> (Q, N) squared Euclidean distances.

    Uses the dot-product expansion (MXU-friendly); values can differ from
    the fused single-query kernel in the last few ulps and tiny negatives
    are possible — callers clamp at zero.
    """
    return _squared_distances_batch(cases, queries,
                                    _resolve_interpret(interpret))


def knn_topk_batch(cases: jax.Array, queries: jax.Array, k: int,
                   interpret: bool | None = None):
    """Batched top-k: (Q, D) queries -> ((Q, k) distances, (Q, k) indices)."""
    d2 = squared_distances_batch(cases, queries, interpret=interpret)
    neg, idx = jax.lax.top_k(-d2, k)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), idx
