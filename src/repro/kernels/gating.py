"""DAG dependency gating: the per-slot gather/scatter that decrements
child in-degree counters when parents finish.

The scan engine (``core/scan_engine.py``) carries a per-row ``pred_left``
vector; each slot it needs ``dec[child] = sum over edges of
fin[parent]`` — a gather over the edge parent list followed by a
segment scatter-add over the edge child list.  Two implementations:

- :func:`dep_decrement` — pure ``jnp`` gather + ``.at[].add`` scatter.
  On XLA:CPU the scatter lowers to a serial per-element loop, so the
  scan engine keeps it only as the fallback for workloads whose max
  in-degree is too wide for the dense transpose.
- :func:`dep_decrement_gather` — the contraction transposed into a
  dense padded predecessor-list gather + row sum; the scan engine's
  default whenever the max in-degree is modest (~6x cheaper on CPU,
  exactly equal counts because integer addition commutes).

Both return identical int32 counts (asserted in
``tests/test_scan_engine.py``); integer arithmetic, so equality is exact
on every backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dep_decrement(fin: jax.Array, parents: jax.Array, children: jax.Array,
                  n: int) -> jax.Array:
    """``dec[c] = #{edges (p, c) with fin[p]}`` as pure jnp ops.

    ``parents``/``children`` may be padded: point padded entries at a row
    whose ``fin`` is always False (the scan engine uses its padding rows).
    """
    contrib = fin[parents].astype(jnp.int32)
    return jnp.zeros(n, dtype=jnp.int32).at[children].add(contrib)


def dep_decrement_gather(fin: jax.Array, pred_rows: jax.Array) -> jax.Array:
    """The same contraction, transposed: ``pred_rows`` is each row's
    padded predecessor list (``(n, max_in_degree)``; padding points at a
    row whose ``fin`` is always False).

    Integer addition, so the counts are exactly equal to the scatter
    form in any summation order — but on XLA:CPU ``.at[].add`` lowers to
    a serial per-element scatter loop (~100us per slot at a few thousand
    edges) while this is one vectorized gather plus a row sum (~6x
    cheaper).  The scan engine uses it whenever the workload's max
    in-degree is small enough for the dense transpose to pay off."""
    return jnp.sum(fin[pred_rows].astype(jnp.int32), axis=1)
