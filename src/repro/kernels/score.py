"""Pallas TPU kernel: fused oracle score construction (Algorithm 1, lines
2–5).

The oracle enumerates (job, scale) entries against T time slots and scores
each cell ``p_j(k) / CI_t`` masked to the entry's feasibility window
``[t_start, t_end)``.  Materialising mask and quotient separately costs
3 HBM round-trips over a (J, T) matrix; the kernel fuses reciprocal,
broadcast-multiply and window masking in one VMEM pass, tiled (BJ, BT).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .knn import _resolve_interpret

BLOCK_J = 256
BLOCK_T = 128


def _score_kernel(marg_ref, ts_ref, te_ref, ci_ref, out_ref, *, block_t):
    _ = pl.program_id(1)          # grid order: (t, j)
    t0 = pl.program_id(0) * block_t
    marg = marg_ref[...].astype(jnp.float32)          # (BJ, 1)
    ts = ts_ref[...].astype(jnp.int32)                # (BJ, 1)
    te = te_ref[...].astype(jnp.int32)
    ci = ci_ref[...].astype(jnp.float32)              # (1, BT)
    t_idx = t0 + jax.lax.broadcasted_iota(jnp.int32, (1, block_t), 1)
    score = marg / jnp.maximum(ci, 1e-9)
    mask = (t_idx >= ts) & (t_idx < te)
    out_ref[...] = jnp.where(mask, score, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def score_matrix(marginals: jax.Array, ci: jax.Array, t_start: jax.Array,
                 t_end: jax.Array, interpret: bool | None = None) -> jax.Array:
    """(J,), (T,), (J,), (J,) -> (J, T) masked scores."""
    j, t = marginals.shape[0], ci.shape[0]
    jp = ((j + BLOCK_J - 1) // BLOCK_J) * BLOCK_J
    tp = ((t + BLOCK_T - 1) // BLOCK_T) * BLOCK_T
    marg = jnp.zeros((jp, 1), jnp.float32).at[:j, 0].set(marginals)
    ts = jnp.zeros((jp, 1), jnp.int32).at[:j, 0].set(t_start)
    te = jnp.zeros((jp, 1), jnp.int32).at[:j, 0].set(t_end)
    civ = jnp.full((1, tp), 1.0, jnp.float32).at[0, :t].set(ci)
    out = pl.pallas_call(
        functools.partial(_score_kernel, block_t=BLOCK_T),
        grid=(tp // BLOCK_T, jp // BLOCK_J),
        in_specs=[
            pl.BlockSpec((BLOCK_J, 1), lambda ti, ji: (ji, 0)),
            pl.BlockSpec((BLOCK_J, 1), lambda ti, ji: (ji, 0)),
            pl.BlockSpec((BLOCK_J, 1), lambda ti, ji: (ji, 0)),
            pl.BlockSpec((1, BLOCK_T), lambda ti, ji: (0, ti)),
        ],
        out_specs=pl.BlockSpec((BLOCK_J, BLOCK_T), lambda ti, ji: (ji, ti)),
        out_shape=jax.ShapeDtypeStruct((jp, tp), jnp.float32),
        interpret=_resolve_interpret(interpret),
    )(marg, ts, te, civ)
    return out[:j, :t]
