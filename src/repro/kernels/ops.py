"""Public wrappers for the Pallas kernels.

``interpret=None`` (the default) resolves through
``knn._resolve_interpret``: the kernels compile to Mosaic on a TPU and run
in the Pallas interpreter on any other backend.  Pass ``interpret`` to
force either mode.
"""
from __future__ import annotations

import jax

from . import flash_attention as _fa
from . import knn as _knn
from . import score as _score


def knn_topk(cases: jax.Array, query: jax.Array, k: int,
             interpret: bool | None = None):
    return _knn.knn_topk(cases, query, k, interpret=interpret)


def knn_topk_batch(cases: jax.Array, queries: jax.Array, k: int,
                   interpret: bool | None = None):
    return _knn.knn_topk_batch(cases, queries, k, interpret=interpret)


def score_matrix(marginals, ci, t_start, t_end, interpret: bool | None = None):
    return _score.score_matrix(marginals, ci, t_start, t_end,
                               interpret=interpret)


def flash_attention(q, k, v, causal_offset: int = 0,
                    interpret: bool | None = None, **kw):
    return _fa.gqa_flash(q, k, v, causal_offset=causal_offset,
                         interpret=interpret, **kw)
