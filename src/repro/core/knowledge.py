"""State featurisation + KNN knowledge base (paper §4.2, Table 2).

The learning phase replays recent traces through the offline oracle and
stores ``STATE -> (m_t, rho_t)`` mappings.  The execution phase queries the
top-k nearest historical states (Euclidean distance over z-scored features;
the paper uses a scikit-learn KD-tree with k=5 — we use a vectorised
brute-force top-k in JAX, with an optional Pallas kernel backend, which is
both simpler and faster at the case-base sizes involved: a few thousand
slots per window).

Aging (paper: "older mappings ... are aged out over a rolling window"): the
base keeps the most recent ``max_windows`` learning windows and drops older
ones on insert.

Hot-path note (EXPERIMENTS.md §Perf): the normalised, weighted case matrix
is computed once per ``_rebuild`` and cached — both as a host array and,
for the jax/pallas backends, as a device-resident ``float32`` array — so a
per-slot query touches only the query vector (O(D)) instead of re-z-scoring
the whole base (O(N·D)) and re-uploading it every slot.  ``add_window``
invalidates the cache.  ``query_batch`` answers Q queries per dispatch
(tiled (Q, N) Pallas distance kernel / one jitted top-k on the other
backends) for sweep-scale workloads.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from .carbon import CarbonService
from .types import Job


def build_state(
    ci: CarbonService,
    t: int,
    queue_counts: np.ndarray,
    mean_elasticity: float,
    arrivals_24h: np.ndarray | None = None,
    rel_backlog: float = 1.0,
) -> np.ndarray:
    """Table-2 state vector: [CI, CI gradient, CI day-ahead rank,
    per-queue (running+paused) job counts ..., per-queue trailing-24h
    arrival counts ..., mean elasticity].

    The trailing-arrival block is our addition to Table 2 (documented in
    EXPERIMENTS.md): in-system queue counts are *policy-dependent* — at
    runtime they drift away from the oracle's trajectory and corrupt the
    match — whereas arrival pressure is a pure function of the trace, so
    its distribution is identical in the learning and execution phases.
    """
    if arrivals_24h is None:
        arrivals_24h = np.zeros_like(np.asarray(queue_counts, dtype=np.float64))
    fc = ci.forecast(t)
    cur = ci.ci(t)
    ratio_min = cur / max(float(np.min(fc)), 1e-9)
    ratio_mean = cur / max(float(np.mean(fc)), 1e-9)
    return np.concatenate(
        [
            np.array([cur, ci.gradient(t), ci.rank(t), ratio_min, ratio_mean]),
            np.asarray(queue_counts, dtype=np.float64),
            np.asarray(arrivals_24h, dtype=np.float64),
            np.array([rel_backlog, mean_elasticity]),
        ]
    )


def relative_backlog(counts_history: np.ndarray) -> np.ndarray:
    """Policy-scale-invariant backlog signal: per-slot total in-system count
    divided by its running mean over the trajectory so far.

    Raw queue counts are policy-dependent (the runtime's backlog equilibrium
    differs from the oracle's), but *relative* deviation from one's own
    typical backlog transfers between the two trajectories.
    """
    counts = np.asarray(counts_history, dtype=np.float64)
    csum = np.cumsum(counts)
    denom = np.maximum(csum / np.arange(1, len(counts) + 1), 1e-9)
    return counts / denom


def states_from_schedule(
    jobs: list[Job],
    alloc: np.ndarray,
    ci: CarbonService,
    num_queues: int,
    t0: int = 0,
) -> np.ndarray:
    """Recompute the Table-2 state at each slot of an oracle run.

    ``alloc`` is the oracle's (N, T) allocation; a job is "in the system" at
    slot t if it has arrived and still has unfinished work (queued, paused,
    or running) — matching the runtime definition used by the simulator.
    """
    n, horizon = alloc.shape
    lengths = np.array([j.length for j in jobs])
    arrivals = np.array([j.arrival for j in jobs])
    queues = np.array([j.queue for j in jobs])
    elast = np.array([j.elasticity() for j in jobs])
    # Cumulative work done by each job before slot t, via the per-job
    # cumulative-throughput lookup table (no per-slot Python).
    kmax = int(alloc.max()) if alloc.size else 0
    thr_tab = np.zeros((n, kmax + 1))
    for i, job in enumerate(jobs):
        for k in range(1, kmax + 1):
            thr_tab[i, k] = job.throughput(k)
    thr = thr_tab[np.arange(n)[:, None], alloc]
    done_after = np.cumsum(thr, axis=1)
    ts = np.arange(horizon)
    done_before = np.concatenate([np.zeros((n, 1)), done_after[:, :-1]], axis=1)
    in_system = (arrivals[:, None] <= ts[None, :]) & \
        (done_before < (lengths - 1e-9)[:, None])               # (n, T)
    recent = (arrivals[:, None] > ts[None, :] - 24) & \
        (arrivals[:, None] <= ts[None, :])                      # (n, T)
    onehot = np.zeros((n, num_queues))
    onehot[np.arange(n), queues] = 1.0
    counts = in_system.T.astype(np.float64) @ onehot            # (T, nq)
    arr24 = recent.T.astype(np.float64) @ onehot                # (T, nq)
    n_in = in_system.sum(axis=0)
    el_sum = in_system.T.astype(np.float64) @ elast
    mean_el = np.where(n_in > 0, el_sum / np.maximum(n_in, 1), 0.0)
    rel = relative_backlog(counts.sum(axis=1))
    states = [
        build_state(ci, t0 + t, counts[t], float(mean_el[t]), arr24[t], rel[t])
        for t in range(horizon)
    ]
    return np.stack(states)


@partial(jax.jit, static_argnames=("k",))
def _knn_jax(cases: jnp.ndarray, query: jnp.ndarray, k: int):
    d2 = jnp.sum((cases - query[None, :]) ** 2, axis=1)
    neg, idx = jax.lax.top_k(-d2, k)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), idx


@partial(jax.jit, static_argnames=("k",))
def _knn_jax_batch(cases: jnp.ndarray, queries: jnp.ndarray, k: int):
    qn = jnp.sum(queries * queries, axis=1, keepdims=True)
    xn = jnp.sum(cases * cases, axis=1)[None, :]
    # HIGHEST: a TPU's default f32 matmul is one bf16 pass (see kernels/knn)
    d2 = qn + xn - 2.0 * jnp.dot(queries, cases.T,
                                 precision=jax.lax.Precision.HIGHEST)
    neg, idx = jax.lax.top_k(-d2, k)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), idx


@dataclasses.dataclass
class KnowledgeBase:
    """Rolling case base of ``STATE -> (m_t, rho_t)`` oracle decisions.

    Distance details (beyond the paper's plain KD-tree Euclidean, which we
    found brittle under closed-loop state drift — see EXPERIMENTS.md):

    - queue-count features are ``log1p``-compressed, since the runtime
      policy's backlog distribution differs from the oracle's and raw counts
      otherwise dominate the metric when out-of-distribution;
    - features carry weights (CI level / day-ahead rank are the
      policy-relevant signal; queue counts provide demand context);
    - neighbour decisions are combined inverse-distance weighted.
    """

    max_windows: int = 8
    k: int = 5
    # "auto" resolves once per instance: brute-force numpy on CPU (a few
    # thousand cases x ~20 features is below the per-call dispatch cost of
    # jax on host), the jitted jax path when an accelerator is attached.
    backend: str = "auto"          # "auto" | "jax" | "pallas" | "numpy"
    # [CI, gradient, rank, queues..., arrivals..., elasticity] — the queue
    # and arrival weights broadcast over their blocks.
    ci_weight: float = 2.0
    rank_weight: float = 2.0
    gradient_weight: float = 1.0
    queue_weight: float = 0.0
    arrival_weight: float = 0.0
    backlog_weight: float = 1.0
    elasticity_weight: float = 0.0
    ratio_weight: float = 2.0
    log_queues: bool = True
    # cache=False recomputes the normalised case matrix on every query (the
    # pre-vectorisation behaviour) — kept for the engine micro-benchmark.
    cache: bool = True
    # None = auto-detect (Pallas interpret mode everywhere but TPU).
    pallas_interpret: bool | None = None

    def __post_init__(self) -> None:
        if self.backend == "auto":
            self.backend = "numpy" if jax.default_backend() == "cpu" else "jax"
        self._windows: deque[tuple[np.ndarray, np.ndarray]] = deque(maxlen=self.max_windows)
        self._dirty = True
        self._X = None
        self._Y = None
        self._mu = None
        self._sigma = None
        self._Xn = None            # normalised, weighted case matrix (host)
        self._Xn_dev = None        # same, device-resident float32

    def _weights(self, dim: int) -> np.ndarray:
        nq = (dim - 7) // 2
        return np.array(
            [self.ci_weight, self.gradient_weight, self.rank_weight,
             self.ratio_weight, self.ratio_weight]
            + [self.queue_weight] * nq
            + [self.arrival_weight] * nq
            + [self.backlog_weight, self.elasticity_weight]
        )

    def _transform(self, x: np.ndarray) -> np.ndarray:
        x = np.array(x, dtype=np.float64, copy=True)
        if self.log_queues:
            x[..., 5:-2] = np.log1p(np.maximum(x[..., 5:-2], 0.0))
        return x

    # --- learning-phase API -------------------------------------------------

    def add_window(self, states: np.ndarray, m_curve: np.ndarray, rho_curve: np.ndarray) -> None:
        y = np.stack([np.asarray(m_curve, np.float64), np.asarray(rho_curve, np.float64)], axis=1)
        self._windows.append((np.asarray(states, np.float64), y))
        self._dirty = True

    def _rebuild(self) -> None:
        xs = [w[0] for w in self._windows]
        ys = [w[1] for w in self._windows]
        self._X = self._transform(np.concatenate(xs)) if xs else np.zeros((0, 1))
        self._Y = np.concatenate(ys) if ys else np.zeros((0, 2))
        self._Xn = None
        self._Xn_dev = None
        if len(self._X):
            self._mu = self._X.mean(axis=0)
            self._sigma = np.maximum(self._X.std(axis=0), 1e-9)
            if self.cache:
                self._Xn = self._normalize_cases()
                if self.backend in ("jax", "pallas"):
                    # one host->device transfer per rebuild, not per query
                    self._Xn_dev = jnp.asarray(self._Xn, jnp.float32)
        self._dirty = False

    def _normalize_cases(self) -> np.ndarray:
        w = self._weights(self._X.shape[1])
        return np.clip((self._X - self._mu) / self._sigma, -3.0, 3.0) * w[None, :]

    def _normalize_query(self, state: np.ndarray) -> np.ndarray:
        """Z-score + clip + weight one state (or a (Q, D) batch of states).

        Clip z-scores: a low-variance feature (e.g. mean elasticity under a
        stable mix) must not dominate the metric when the runtime drifts
        slightly out of the training distribution."""
        w = self._weights(self._X.shape[1])
        q = self._transform(np.asarray(state, np.float64))
        return np.clip((q - self._mu) / self._sigma, -3.0, 3.0) * w

    def _cases(self) -> np.ndarray:
        if self._Xn is not None:
            return self._Xn
        return self._normalize_cases()

    def _cases_dev(self) -> jnp.ndarray:
        if self._Xn_dev is not None:
            return self._Xn_dev
        return jnp.asarray(self._cases(), jnp.float32)

    def __len__(self) -> int:
        if self._dirty:
            self._rebuild()
        return len(self._X)

    def rho_values(self) -> np.ndarray:
        """All stored oracle rho decisions (the learned marginal-capacity
        curve's samples) — ``carbonflex-scale`` derives its scale-up
        threshold from their median (core/mpc.py)."""
        if self._dirty:
            self._rebuild()
        return self._Y[:, 1] if len(self._X) else np.zeros(0)

    # --- execution-phase API ------------------------------------------------

    def _prepare(self, state: np.ndarray, k: int | None):
        if self._dirty:
            self._rebuild()
        if not len(self._X):
            raise RuntimeError("empty knowledge base — run a learning window first")
        return min(k or self.k, len(self._X)), self._normalize_query(state)

    def query(self, state: np.ndarray, k: int | None = None):
        """Top-k nearest cases.  Returns (m_values, rho_values, distances)."""
        k, q = self._prepare(state, k)
        if self.backend == "numpy":
            xs = self._cases()
            d2 = np.sum((xs - q[None, :]) ** 2, axis=1)
            idx = np.argpartition(d2, k - 1)[:k]
            idx = idx[np.argsort(d2[idx])]
            dist = np.sqrt(d2[idx])
        elif self.backend == "pallas":
            from repro.kernels import knn as knn_kernel

            dist, idx = knn_kernel.knn_topk(
                self._cases_dev(), jnp.asarray(q, jnp.float32), k,
                interpret=self.pallas_interpret)
            dist, idx = np.asarray(dist), np.asarray(idx)
        else:
            dist, idx = _knn_jax(self._cases_dev(), jnp.asarray(q, jnp.float32), k)
            dist, idx = np.asarray(dist), np.asarray(idx)
        return self._Y[idx, 0], self._Y[idx, 1], dist

    def query_batch(self, states: np.ndarray, k: int | None = None):
        """Top-k nearest cases for a (Q, D) batch of states in one dispatch.

        Returns ((Q, k) m_values, (Q, k) rho_values, (Q, k) distances).
        Distances use the MXU-friendly dot-product expansion and can differ
        from ``query`` in the final ulps (ties may reorder)."""
        states = np.atleast_2d(np.asarray(states, np.float64))
        k, qs = self._prepare(states, k)
        if self.backend == "numpy":
            xs = self._cases()
            qn = np.sum(qs * qs, axis=1, keepdims=True)
            xn = np.sum(xs * xs, axis=1)[None, :]
            d2 = np.maximum(qn + xn - 2.0 * qs @ xs.T, 0.0)
            idx = np.argpartition(d2, k - 1, axis=1)[:, :k]
            order = np.argsort(np.take_along_axis(d2, idx, axis=1), axis=1)
            idx = np.take_along_axis(idx, order, axis=1)
            dist = np.sqrt(np.take_along_axis(d2, idx, axis=1))
        elif self.backend == "pallas":
            from repro.kernels import knn as knn_kernel

            dist, idx = knn_kernel.knn_topk_batch(
                self._cases_dev(), jnp.asarray(qs, jnp.float32), k,
                interpret=self.pallas_interpret)
            dist, idx = np.asarray(dist), np.asarray(idx)
        else:
            dist, idx = _knn_jax_batch(self._cases_dev(),
                                       jnp.asarray(qs, jnp.float32), k)
            dist, idx = np.asarray(dist), np.asarray(idx)
        return self._Y[idx, 0], self._Y[idx, 1], dist
