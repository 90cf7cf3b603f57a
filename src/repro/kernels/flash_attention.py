"""Pallas TPU kernel: GQA causal flash attention (online softmax).

Block-tiled for the MXU: Q tiles of (BLOCK_Q, D) stream against K/V tiles
of (BLOCK_K, D) held in VMEM; the running (m, l, acc) online-softmax state
lives in VMEM scratch and is carried across the innermost (sequential) KV
grid dimension.  GQA is handled in the index maps: query head h reads KV
head ``h // group`` — no KV replication in HBM.

Grid: (batch, q_heads, nQ, nK) with ``dimension_semantics = (parallel,
parallel, parallel, arbitrary)``; the output tile is written at the last
KV step.  Validated against ``ref.flash_attention_ref``; compiled to
Mosaic on a TPU and interpreted elsewhere (``knn._resolve_interpret``).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .knn import _resolve_interpret

BLOCK_Q = 128
BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, scale, block_q, block_k, seq_k, causal_offset, n_k):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                  # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = causal_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = (q_pos >= k_pos) & (k_pos < seq_k)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                               # (BQ, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _emit():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal_offset", "interpret",
                                             "block_q", "block_k"))
def gqa_flash(q: jax.Array, k: jax.Array, v: jax.Array,
              causal_offset: int = 0, interpret: bool | None = None,
              block_q: int = BLOCK_Q, block_k: int = BLOCK_K) -> jax.Array:
    """q: (B, Sq, H, D); k/v: (B, Sk, KV, D) -> (B, Sq, H, D), causal."""
    interpret = _resolve_interpret(interpret)
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    group = hq // hkv
    scale = 1.0 / np.sqrt(d)

    sq_p = ((sq + block_q - 1) // block_q) * block_q
    sk_p = ((sk + block_k - 1) // block_k) * block_k
    qt = jnp.moveaxis(q, 2, 1)                        # (B, H, Sq, D)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if sq_p != sq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    n_q, n_k = sq_p // block_q, sk_p // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        seq_k=sk, causal_offset=causal_offset, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=(b, hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
    )(qt, kt, vt)
    out = out[:, :, :sq]
    return jnp.moveaxis(out, 1, 2)
