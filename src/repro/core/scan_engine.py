"""The scan engine: the per-slot simulation loop as a jitted ``lax.scan``.

``BENCH_engine.json`` showed the numpy vector engine's wins collapsing
exactly where the interesting policies live (geo-flex 1.1x, dag-carbon
1.4x vs 3.9-4.1x for simple policies): every slot still round-trips
through Python for the policy decision and the defensive trimming.  This
module lifts the whole slot loop onto the device:

- the *decision* of every nativizable policy is expressed as packed
  array ops inside the scan step (threshold-fill for the single-region
  family, a sequential candidate walk for the geo family);
- admission, dependency gating (pred-count decrement via
  ``kernels/gating.py``), release and deadline-from-release live in the
  carried state;
- whole (seeds x policies x regions x forecasts) grids run as one
  vmapped device program (`simulate_many_scan`), chunked so termination
  is checked on the host between chunks.

Bit-parity contract
-------------------
``engine="scan"`` is **bit-identical** to the scalar/vector references
(asserted across policy families in ``tests/test_scan_engine.py``).  Two
mechanisms make that possible on a backend whose compiler contracts
``a*b + c`` into fused-multiply-add (XLA CPU does, measurably):

1. *No float accounting on device.*  The scan emits only the boolean
   ``take`` grid (which rows ran which slot); the host replays
   fractional progress from it — ``frac = min(1, rem/thr)`` then
   ``rem -= thr`` per slot, single correctly-rounded ops in the same
   order the vector engine performs them — and feeds the exact numpy
   energy expressions over the resulting cells.  Booleans also shrink
   the device->host transfer ~8x vs shipping float grids.
2. *Host-precomputed decision tables.*  Threshold eligibility
   (``percentile_threshold``/quantile views), geo forecast window-means
   and percentile thresholds are computed host-side per chunk with the
   policies' own numpy expressions, then consumed on device as data.
   (Window-mean tables are bitwise equal to the per-slot slices the
   policies take — ``np.mean`` over a leading slice is associativity-
   stable across the batched and scalar forms.)

The single remaining device-side float *combination* is the geo
migration economics ``mean*e_run + mig_carbon`` (one add), where FMA
contraction can differ from numpy in the last ulp; a decision flips only
on an exact tie between move and stay — measure-zero on real traces and
pinned empirically by the randomized parity suite.  A TPU has no native
float64 and emulates it; ``chip_smoke.py`` holds every scan-native
family, geo included, bit-equal to the scalar engine on one.

Native coverage and delegation
------------------------------
Natively scanned (exact policy types, ``faults is None``):

- single-region: ``carbon-agnostic``, ``dag-fcfs``, ``wait-awhile``,
  ``wait-awhile-robust``, ``dag-carbon``, ``dag-cap`` (the
  threshold-fill family — FCFS at ``k_min`` under an eligibility mask);
- MPC: ``carbonflex-mpc`` / ``carbonflex-scale`` (``core/mpc.py``) — the
  receding-horizon rule consumes its host-precomputed rank/need/clean
  tables as per-slot xs and row constants, so the whole horizon search
  runs inside the scan step as integer gathers; the scale variant's
  per-slot allocations ride back in a ``scaled`` boolean grid that the
  host energy replay resolves to per-cell k;
- geo: ``geo-static``, ``geo-greedy``, ``geo-flex``.

Everything else (host-stateful planners like gaia/carbonscaler/
carbonflex/oracle, policy subclasses, and *any* faulted case — fault
processes draw from host RNG streams mid-slot) transparently delegates
to the numpy vector engine, which is itself bit-identical to the scalar
reference.  Carbon-feed *outages* (degraded CI views) are pure per-slot
functions and run natively.  This is an honest trade: the scan engine
accelerates exactly the policy structure that is expressible as packed
array ops, and ``engine="scan"`` is always safe to request.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import emissions
from .baselines import (CarbonAgnosticPolicy, RobustWaitAwhilePolicy,
                        WaitAwhilePolicy)
from .carbon import CarbonService, MultiRegionCarbonService
from .dag import DagCapPolicy, DagCarbonPolicy, DagFcfsPolicy
from .forecast import PerfectForecast, QuantileCIView
from .geo import GeoFlexPolicy, GeoGreedyPolicy, GeoStaticPolicy
from .mpc import CarbonFlexMPCPolicy, CarbonFlexScalePolicy
from .types import GeoCluster, SimResult, SlotLog
from ..telemetry import PhaseProfiler, Telemetry, span

_EPS = 1e-9
_log = logging.getLogger(__name__)
_BIG_T = np.int64(2 ** 62)     # arrival sentinel for padding rows
ROW_PAD = 256                  # row-count bucket (bounds jit recompiles)
EDGE_PAD = 256
MAX_GATHER_DEG = 64            # in-degree bound for the dense dep transpose
CHUNK = 168                    # slots per device dispatch (horizon region)
OVERRUN_CHUNK = 24             # slots per dispatch past the horizon
BATCH_TILE = 64                # vmapped cells per dispatch (memory bound)


# --- native-policy detection -------------------------------------------------

_MPC_KINDS = {"mpc", "mpc-scale"}
_SINGLE_KINDS = {"plain", "thresh", "cap"} | _MPC_KINDS


def native_kind(policy, cluster, faults) -> str | None:
    """The scan-native program family for this case, or None to delegate.

    Exact ``type()`` checks: a subclass may override ``decide`` in ways
    the packed decision tables cannot see, so only the known closed set
    runs natively (``carbonflex-scale`` is checked before its base MPC
    class for the same reason).  Any fault process delegates (host RNG
    mid-slot).
    """
    if faults is not None:
        return None
    if isinstance(cluster, GeoCluster):
        return {GeoStaticPolicy: "geo-static", GeoGreedyPolicy: "geo-greedy",
                GeoFlexPolicy: "geo-flex"}.get(type(policy))
    tp = type(policy)
    if tp in (CarbonAgnosticPolicy, DagFcfsPolicy):
        return "plain"
    if tp in (WaitAwhilePolicy, RobustWaitAwhilePolicy, DagCarbonPolicy):
        return "thresh"
    if tp is DagCapPolicy:
        return "cap"
    if tp is CarbonFlexScalePolicy:
        return "mpc-scale"
    if tp is CarbonFlexMPCPolicy:
        return "mpc"
    return None


def _pad_rows(n: int) -> int:
    """Smallest ROW_PAD multiple strictly greater than n (the last row is
    always padding — the dependency gating points its edge padding there)."""
    return (n // ROW_PAD + 1) * ROW_PAD


# --- batched CI-table fast paths ---------------------------------------------
# The per-slot CI/forecast APIs (``ci_vec``/``forecast_matrix``/``ci``)
# are Python calls; building a week of decision tables through them costs
# more than the device program itself.  When the view is a plain
# perfect-forecast service the same tables fall out of whole-trace
# indexing — the gathered elements are the identical float64 values the
# per-slot calls return, so the fast path is bitwise equal; any other
# view (forecast models, outage-degraded, subclasses) keeps the
# per-slot loop.


def _perfect_traces(ci_pol) -> np.ndarray | None:
    """(R, T) trace stack when every regional feed is a plain
    perfect-forecast ``CarbonService`` with no outage; None otherwise."""
    if type(ci_pol) is not MultiRegionCarbonService:
        return None
    svs = ci_pol.services
    if any(type(s) is not CarbonService or type(s.model) is not PerfectForecast
           or s.outage is not None or np.asarray(s.trace).dtype != np.float64
           for s in svs):
        return None
    if len({len(s.trace) for s in svs}) != 1:
        return None
    return np.stack([np.asarray(s.trace) for s in svs])


def _ci_vec_block(ci_pol, ts: np.ndarray) -> np.ndarray:
    """(S, R) stack of ``ci_vec`` over the slots ``ts``."""
    tr = _perfect_traces(ci_pol)
    if tr is not None and ts[0] >= 0:
        return tr[:, np.minimum(ts, tr.shape[1] - 1)].T.copy()
    return np.stack([ci_pol.ci_vec(int(t)) for t in ts])


def _forecast_block(ci_pol, ts: np.ndarray, h: int) -> np.ndarray:
    """(S, R, H) stack of ``forecast_matrix`` over the slots ``ts``.

    The fast path mirrors ``forecast._truth_slice`` exactly: windows past
    the trace end repeat the last known value (the padded-trace gather
    reads that same element)."""
    tr = _perfect_traces(ci_pol)
    if tr is not None and ts[0] >= 0 and ts[-1] < tr.shape[1]:
        pad = np.concatenate([tr, np.repeat(tr[:, -1:], h - 1, axis=1)],
                             axis=1)
        idx = ts[:, None] + np.arange(h)[None, :]
        return pad[:, idx].transpose(1, 0, 2)
    return np.stack([ci_pol.forecast_matrix(int(t), h) for t in ts])


def _ci_block(ci, t0: int, n_valid: int) -> np.ndarray:
    """Accounting CI per slot (true service; outages never apply here)."""
    if type(ci) is CarbonService:
        # float64 widening is exact, matching the per-slot float() calls
        tr = np.asarray(ci.trace, dtype=np.float64)
        return tr[np.minimum(np.arange(t0, t0 + n_valid), len(tr) - 1)]
    return np.array([ci.ci(t0 + i) for i in range(n_valid)])


def _ci_vec_acct_block(mci, t0: int, n_valid: int) -> np.ndarray:
    """(S, R) accounting CI vectors (true multi-region service)."""
    ts = np.arange(t0, t0 + n_valid)
    if type(mci) is MultiRegionCarbonService:
        return np.stack(
            [np.asarray(s.trace, dtype=np.float64)[
                np.minimum(ts, len(s.trace) - 1)] for s in mci.services],
            axis=1)
    return np.stack([mci.ci_vec(int(t)) for t in ts]) if n_valid \
        else np.zeros((0, mci.n_regions))


# --- single-region program ---------------------------------------------------


@dataclasses.dataclass
class _SingleProgram:
    """Device constants + host mirrors for one single-region native case."""

    consts: dict                   # jnp arrays / 0-d scalars
    carry0: dict
    n_pad: int
    kind: str                      # plain | thresh | cap | mpc | mpc-scale
    uniform: bool                  # all k_min equal -> cumsum fill
    deps: str                      # none | gather | scatter (gating form)
    xs_fn: Callable                # (ts: np.ndarray) -> host per-slot tables
    xs_dims: tuple                 # xs table shapes (part of the batch key)
    # host accounting mirrors
    power: np.ndarray
    m_t: int
    k_up: np.ndarray | None = None     # mpc-scale: per-row clean-slot k


def _single_elig_fn(policy, ci_pol, kind: str) -> Callable:
    """Per-slot low-carbon eligibility flags, computed with the policy's
    own expressions (bit-parity by construction)."""
    if kind == "plain":
        return lambda ts: np.ones(len(ts), dtype=bool)
    view = ci_pol
    if type(policy) is RobustWaitAwhilePolicy:
        view = QuantileCIView(ci_pol, policy.quantile)
    pct = policy.percentile

    tr = pad_tr = None
    if (type(view) is CarbonService and type(view.model) is PerfectForecast
            and view.outage is None
            and np.asarray(view.trace).dtype == np.float64):
        # perfect-forecast fast path: whole-trace windows are the same
        # float64 elements the per-slot forecast() calls slice (see
        # _forecast_block), so the batched percentile is bitwise equal
        tr = np.asarray(view.trace)
        hor = int(view.horizon)
        pad_tr = np.concatenate([tr, np.full(hor - 1, tr[-1])])

    def elig(ts: np.ndarray) -> np.ndarray:
        if tr is not None and ts[0] >= 0 and ts[-1] < len(tr):
            civ = tr[np.minimum(ts, len(tr) - 1)]
            fcm = pad_tr[ts[:, None] + np.arange(hor)[None, :]]
            return civ <= np.percentile(fcm, pct, axis=1) + 1e-12
        # one percentile call over the stacked windows: np.percentile
        # with axis= partitions + interpolates each row with the same
        # arithmetic as the per-row call, so this is bitwise identical
        # to the policies' per-slot `percentile_threshold(t, pct)` (and
        # ~5x cheaper — the per-call numpy overhead dominated the sweep
        # profile); rows of unequal length (trace tail) fall back.
        tl = ts.tolist()
        civ = np.array([view.ci(t) for t in tl])
        fcs = [view.forecast(t) for t in tl]
        if fcs and all(len(f) == len(fcs[0]) for f in fcs):
            thresh = np.percentile(np.stack(fcs), pct, axis=1)
        else:
            thresh = np.array([float(np.percentile(f, pct)) for f in fcs])
        return civ <= thresh + 1e-12

    return elig


def _build_single(packed, cluster, policy, ci_pol, kind: str,
                  t0: int, horizon: int,
                  prof: PhaseProfiler | None = None) -> _SingleProgram:
    n = packed.n
    n_pad = _pad_rows(n)
    power = np.where(packed.power > 0, packed.power, cluster.power_per_server)
    kmin = packed.k_min
    thr = packed.thr_tab[np.arange(n), kmin]
    i64, f64 = np.int64, np.float64

    def padded(src, fill, dtype):
        out = np.full(n_pad, fill, dtype=dtype)
        out[:n] = src
        return out

    arrival = padded(packed.arrival, _BIG_T, i64)
    elig_row = np.zeros(n_pad, dtype=bool)
    if kind == "plain":
        elig_row[:n] = True
    elif kind == "cap":
        # criticality is static per window (DagCapPolicy.on_window_start);
        # a job missing from the map is critical (crit.get(..., True))
        crit = policy._critical
        elig_row[:n] = [bool(crit.get(int(j), True))
                        for j in packed.job_ids.tolist()]

    deps = "none"
    dep_consts: dict = {}
    if packed.has_deps:
        deg = np.diff(packed.succ_ptr[:n + 1])
        par = np.repeat(np.arange(n, dtype=i64), deg)
        chd = packed.succ_rows[
            packed.succ_ptr[0]:packed.succ_ptr[n]].astype(i64)
        ind = np.bincount(chd, minlength=n) if len(chd) \
            else np.zeros(n, dtype=i64)
        d_max = int(ind.max()) if len(chd) else 0
        if d_max <= MAX_GATHER_DEG:
            # transposed gating: per-row padded predecessor lists (the
            # dense (n_pad, D) gather beats XLA:CPU's serial scatter by
            # ~6x for the bounded in-degrees real DAG workloads have)
            deps = "gather"
            d_pad = max(4, -4 * (-max(d_max, 1) // 4))
            pred_rows = np.full((n_pad, d_pad), n_pad - 1, dtype=i64)
            order = np.argsort(chd, kind="stable")
            sc, sp = chd[order], par[order]
            starts = np.concatenate([[0], np.cumsum(ind)])
            pred_rows[sc, np.arange(len(sc)) - starts[sc]] = sp
            dep_consts["pred_rows"] = pred_rows
        else:
            deps = "scatter"
            e_pad = max(EDGE_PAD, ((len(par) + EDGE_PAD - 1) // EDGE_PAD)
                        * EDGE_PAD)
            parents = np.full(e_pad, n_pad - 1, dtype=i64)
            children = np.full(e_pad, n_pad - 1, dtype=i64)
            parents[:len(par)] = par
            children[:len(chd)] = chd
            dep_consts["parents"] = parents
            dep_consts["children"] = children

    k_up = None
    mpc_consts: dict = {}
    if kind in _MPC_KINDS:
        # the MPC rule's row constants: static job length (``remaining``
        # in the carry decays, done-work needs the original), queue ids
        # for the need-LUT gather, and the learned need LUT itself
        mpc_consts["length_c"] = padded(packed.length, 0.0, f64)
        mpc_consts["queue"] = padded(packed.queue, 0, i64)
        mpc_consts["need_lut"] = policy.scan_tables()["need_lut"]
        if kind == "mpc-scale":
            k_up = np.asarray(policy._k_up, dtype=i64)
            mpc_consts["k_scale"] = padded(k_up, 1, i64)
            mpc_consts["thr_up"] = padded(
                packed.thr_tab[np.arange(n), k_up], 1.0, f64)

    # one device_put for the whole tree (per-array jnp.asarray dispatch
    # was a measurable share of short runs)
    consts = jax.device_put(dict(
        arrival=arrival,
        kmin=padded(kmin, 1, i64),
        thr=padded(thr, 1.0, f64),
        thr_guard=padded(np.maximum(thr, 1e-9), 1.0, f64),
        dl_span=padded(packed.dl_span, 0, i64),
        elig_row=elig_row,
        m_cap=i64(cluster.capacity),
        n_real=i64(n),
        t_end=i64(t0 + horizon),
        **dep_consts,
        **mpc_consts,
    ))
    carry0 = jax.device_put(dict(
        remaining=padded(packed.length, 0.0, f64),
        slack=padded([j.delay for j in packed.jobs], 0, i64),
        waited=np.zeros(n_pad, dtype=i64),
        deadline_eff=padded(packed.deadline, 0, i64),
        pred_left=padded(packed.pred0, 0, i64),
        in_sys=np.zeros(n_pad, dtype=bool),
        finished=np.zeros(n_pad, dtype=bool),
        pending=np.zeros(n_pad, dtype=bool),
        ended=np.asarray(False),
    ))
    if prof is not None:
        prof.count("h2d_bytes", _nbytes((consts, carry0)))
    if kind in _MPC_KINDS:
        # per-slot tables of the MPC rule, straight from the policy's own
        # host-precomputed arrays (bit-parity by construction)
        def xs_fn(ts: np.ndarray) -> dict:
            xs = {"t": ts.astype(i64),
                  "rank_t": policy.rank_rows(ts).astype(i64)}
            if kind == "mpc-scale":
                xs["clean_t"] = policy.clean_rows(ts)
            return xs

        xs_dims = (int(policy.cfg.horizon), mpc_consts["need_lut"].shape)
    else:
        elig = _single_elig_fn(policy, ci_pol, kind)

        def xs_fn(ts: np.ndarray) -> dict:
            return {"t": ts.astype(i64), "elig_t": elig(ts)}

        xs_dims = ()

    # per-slot scale-up makes the requested k slot-varying -> the cumsum
    # fill's uniform-k premise no longer holds
    uniform = bool(n > 0 and (kmin == kmin[0]).all()
                   and kind != "mpc-scale")
    return _SingleProgram(
        consts=consts, carry0=carry0, n_pad=n_pad, kind=kind,
        uniform=uniform, deps=deps, xs_fn=xs_fn, xs_dims=xs_dims,
        power=power, m_t=int(cluster.capacity), k_up=k_up)


def _single_step(consts, carry, x, *, kind: str, uniform: bool, deps: str):
    """One engine slot (mirrors ``_simulate_vector``'s loop body)."""
    t = x["t"]
    rem = carry["remaining"]
    slack = carry["slack"]
    waited = carry["waited"]
    dle = carry["deadline_eff"]
    pred = carry["pred_left"]
    in_sys = carry["in_sys"]
    fin_all = carry["finished"]
    pending = carry["pending"]
    n_pad = rem.shape[0]

    # release (DAG): tasks whose last predecessor finished last slot —
    # slack/deadline count from the release slot
    if deps != "none":
        in_sys = in_sys | pending
        dle = jnp.where(pending, t + consts["dl_span"], dle)
        pending = jnp.zeros_like(pending)
    # admission: arrival passed, not finished, not gated
    arrived = consts["arrival"] <= t
    in_sys = in_sys | (arrived & ~fin_all & (pred == 0))

    n_in = jnp.sum(in_sys)
    n_arr = jnp.sum(arrived)
    blocked = n_arr - n_in - jnp.sum(fin_all)
    ended = carry["ended"] | ((n_in == 0) & (n_arr == consts["n_real"])
                              & (blocked == 0) & (t >= consts["t_end"]))
    act = in_sys & ~ended

    # decision: FCFS threshold-fill at k_min (rows are (arrival, job_id)-
    # sorted, so forced-then-unforced in row order IS the FCFS key)
    forced = slack <= 0
    live = rem > _EPS
    kmin = consts["kmin"]
    m_cap = consts["m_cap"]
    if kind in _MPC_KINDS:
        # MPC eligibility: current slot among the job's estimated-need
        # cheapest within its feasible window (CarbonFlexMPCPolicy.decide
        # — same tables, same integer logic)
        didx = jnp.clip(jnp.floor(consts["length_c"] - rem)
                        .astype(jnp.int64), 0,
                        consts["need_lut"].shape[1] - 1)
        need = consts["need_lut"][consts["queue"], didx]
        w = jnp.clip(slack + need, 1, x["rank_t"].shape[-1])
        cand = act & live & (forced | (x["rank_t"][w - 1] < need))
    else:
        cand = act & live & (forced | x["elig_t"] | consts["elig_row"])
    if kind == "mpc-scale":
        # clean-window scale-up: unforced rows request the learned k_up
        kreq = jnp.where(forced | ~x["clean_t"], kmin, consts["k_scale"])
    else:
        kreq = kmin
    if uniform:
        # uniform k: "continue" fill == rank-prefix per group
        k0 = kmin[0]
        cf = cand & forced
        cr = cand & ~forced
        tf = cf & (jnp.cumsum(cf.astype(jnp.int64)) * k0 <= m_cap)
        used_f = k0 * jnp.sum(tf)
        tr = cr & (used_f + jnp.cumsum(cr.astype(jnp.int64)) * k0 <= m_cap)
        take = tf | tr
    else:
        idx = jnp.arange(n_pad, dtype=jnp.int64)
        key = jnp.where(cand, (~forced).astype(jnp.int64) * n_pad + idx,
                        jnp.int64(2 * n_pad))
        order = jnp.argsort(key, stable=True)

        def fill(used, row):
            ok = cand[row] & (used + kreq[row] <= m_cap)
            return used + jnp.where(ok, kreq[row], 0), ok

        # unroll: the fill body is a handful of scalar ops, so XLA:CPU's
        # per-iteration while-loop dispatch dominates — unrolling trades
        # code size for ~5x less loop overhead (bit-identical: same ops,
        # same order, just fewer loop-carried jumps).
        _, take_o = lax.scan(fill, jnp.int64(0), order, unroll=16)
        take = jnp.zeros_like(cand).at[order].set(take_o)

    # progress (energy + frac replay host-side from take; see module doc)
    if kind == "mpc-scale":
        scaled = take & (kreq > kmin)
        rem2 = jnp.where(take, rem - jnp.where(scaled, consts["thr_up"],
                                               consts["thr"]), rem)
    else:
        rem2 = jnp.where(take, rem - consts["thr"], rem)
    wmask = act & live & ~take
    slack2 = jnp.where(wmask, slack - 1, slack)
    waited2 = jnp.where(wmask, waited + 1, waited)

    fin = act & (rem2 <= _EPS)
    viol = fin & (t > dle)
    waited_fin = jnp.where(fin, waited2, 0)
    fin_all2 = fin_all | fin
    in_sys2 = in_sys & ~fin
    if deps != "none":
        from repro.kernels import gating
        if deps == "gather":
            dec = gating.dep_decrement_gather(fin, consts["pred_rows"])
        else:
            dec = gating.dep_decrement(fin, consts["parents"],
                                       consts["children"], n_pad)
        pred2 = pred - dec.astype(jnp.int64)
        pending2 = (dec > 0) & (pred2 == 0) & arrived
    else:
        pred2, pending2 = pred, pending

    carry2 = dict(remaining=rem2, slack=slack2, waited=waited2,
                  deadline_eff=dle, pred_left=pred2, in_sys=in_sys2,
                  finished=fin_all2, pending=pending2, ended=ended)
    # ys is the device->host transfer per slot, so it is kept lean: the
    # boolean take mask replaces the f64 frac/k_vec grids (the host
    # replays remaining/frac/energy from it exactly), counters fit int32
    ys = dict(take=take, fin=fin, viol=viol,
              waited_fin=waited_fin.astype(jnp.int32),
              n_rows=n_in.astype(jnp.int32), ended=ended)
    if kind == "mpc-scale":
        ys["scaled"] = scaled
    return carry2, ys


@functools.partial(jax.jit, static_argnames=("kind", "uniform", "deps"))
def _single_chunk(consts, carry, xs, kind: str, uniform: bool, deps: str):
    step = functools.partial(_single_step, consts, kind=kind,
                             uniform=uniform, deps=deps)
    return lax.scan(lambda c, x: step(c, x), carry, xs)


@functools.partial(jax.jit, static_argnames=("kind", "uniform", "deps"))
def _single_chunk_batch(consts, carry, xs, kind: str, uniform: bool,
                        deps: str):
    def one(c, ca, x):
        step = functools.partial(_single_step, c, kind=kind,
                                 uniform=uniform, deps=deps)
        return lax.scan(lambda cc, xx: step(cc, xx), ca, x)

    return jax.vmap(one)(consts, carry, xs)


# --- geo program -------------------------------------------------------------


@dataclasses.dataclass
class _GeoProgram:
    consts: dict
    carry0: dict
    n_pad: int
    kind: str                      # geo-static | geo-greedy | geo-flex
    uniform: bool                  # all k_min equal -> fill-key fixpoint
    xs_fn: Callable                # (ts) -> dict of per-slot tables
    power: np.ndarray
    mig_e: np.ndarray              # host transfer energy per row
    caps: np.ndarray
    mig_vals: list


def _build_geo(packed, geo: GeoCluster, policy, ci_pol,
               t0: int, horizon: int, kind: str,
               prof: PhaseProfiler | None = None) -> _GeoProgram:
    n = packed.n
    n_pad = _pad_rows(n)
    n_regions = geo.n_regions
    caps = geo.capacity_vec()
    power = np.where(packed.power > 0, packed.power, geo.power_per_server)
    kmin = packed.k_min
    thr = packed.thr_tab[np.arange(n), kmin]
    i64, f64 = np.int64, np.float64

    def padded(src, fill, dtype):
        out = np.full(n_pad, fill, dtype=dtype)
        out[:n] = src
        return out

    mig_slots = np.array([geo.migration.slots(j) for j in packed.jobs],
                         dtype=i64)
    mig_e = np.array([geo.migration.energy_kwh(j) for j in packed.jobs],
                     dtype=f64)
    mig_vals = sorted(set(mig_slots.tolist())) or [0]
    val2idx = {v: i for i, v in enumerate(mig_vals)}
    mig_idx = np.array([val2idx[int(v)] for v in mig_slots], dtype=i64)
    home = np.array([geo.home_region(i) for i in range(n)], dtype=i64)
    # e_run coefficient: ((k_min * power) * slot_hours), the first three
    # factors of both the energy expression and the policies' e_run
    ec = (kmin * power) * geo.slot_hours

    lookahead = getattr(policy, "lookahead", 24)
    percentile = getattr(policy, "percentile", 40.0)
    margin_c = 1.0 - getattr(policy, "saving_margin", 0.0)
    max_moves = int(getattr(policy, "max_migrations_per_job", 0))

    consts = jax.device_put(dict(
        arrival=padded(packed.arrival, _BIG_T, i64),
        kmin=padded(kmin, 1, i64),
        thr=padded(thr, 1.0, f64),
        thr_guard=padded(np.maximum(thr, 1e-9), 1.0, f64),
        deadline=padded(packed.deadline, 0, i64),
        ec=padded(ec, 0.0, f64),
        mig_e=padded(mig_e, 0.0, f64),
        mig_slots=padded(mig_slots, 0, i64),
        mig_idx=padded(mig_idx, 0, i64),
        caps=caps.astype(i64),
        margin_c=f64(margin_c),
        max_moves=i64(max_moves),
        n_real=i64(n),
        t_end=i64(t0 + horizon),
    ))
    carry0 = jax.device_put(dict(
        remaining=padded(packed.length, 0.0, f64),
        slack=padded([j.delay for j in packed.jobs], 0, i64),
        waited=np.zeros(n_pad, dtype=i64),
        in_sys=np.zeros(n_pad, dtype=bool),
        finished=np.zeros(n_pad, dtype=bool),
        started=np.zeros(n_pad, dtype=bool),
        placed=np.zeros(n_pad, dtype=bool),
        pol_region=padded(home, 0, i64),
        eng_region=padded(home, 0, i64),
        mig_left=np.zeros(n_pad, dtype=i64),
        moves=np.zeros(n_pad, dtype=i64),
        ended=np.asarray(False),
    ))
    if prof is not None:
        prof.count("h2d_bytes", _nbytes((consts, carry0)))

    # Per-chunk host decision tables (``_collect_chunks`` uploads each
    # chunk's tables in one device_put).  The CI/forecast blocks go
    # through the batched whole-trace fast paths above (the per-slot
    # Python API calls cost more than the device program); batched slice
    # means are bitwise equal to the per-slot `fc[:, :h].mean(axis=1)`
    # the policy computes (same pairwise reduction over the same values —
    # ascontiguousarray only changes strides, never the reduction order).
    def xs_fn(ts: np.ndarray) -> dict:
        s = len(ts)
        xs = {"t": ts.astype(i64)}
        if kind == "geo-static":
            return xs
        civ = _ci_vec_block(ci_pol, ts)                           # (S, R)
        xs["ci_now"] = civ
        if kind == "geo-greedy":
            xs["clean_order"] = np.argsort(civ, axis=1,
                                           kind="stable").astype(i64)
            return xs
        fc = np.ascontiguousarray(
            _forecast_block(ci_pol, ts, lookahead))               # (S, R, H)
        xs["thresh_eps"] = np.percentile(fc, percentile, axis=2) + _EPS
        means = np.zeros((s, n_regions, lookahead))
        for h in range(1, lookahead + 1):
            means[:, :, h - 1] = fc[:, :, :h].mean(axis=2)
        xs["means"] = means
        movem = np.zeros((s, len(mig_vals), n_regions, lookahead))
        for mi, ms in enumerate(mig_vals):
            for h in range(1, lookahead - ms + 1):
                movem[:, mi, :, h - 1] = fc[:, :, ms:ms + h].mean(axis=2)
        xs["movemeans"] = movem
        return xs

    return _GeoProgram(consts=consts, carry0=carry0, n_pad=n_pad, kind=kind,
                       uniform=bool((kmin == kmin[0]).all()), xs_fn=xs_fn,
                       power=power, mig_e=mig_e, caps=caps,
                       mig_vals=mig_vals)


def _geo_step(consts, carry, x, *, kind: str, lookahead: int,
              uniform: bool):
    """One geo engine slot (mirrors ``_simulate_geo_vector`` + the geo
    policies' ``decide_geo`` + ``_resolve_geo``).

    Two exact implementations of the FCFS capacity walk:

    - ``uniform=True`` (every job requests the same ``k_min``): region
      fullness along the walk is binary and monotone, so the walk's
      outcome is characterised by one *fill key* per region — the FCFS
      key of the allocation that consumed the region's last slice; a row
      sees the region open iff its key is <= that.  The fill keys are the
      unique fixpoint of a monotone (non-increasing, componentwise) map,
      found by iterating the fully vectorised round below from "nothing
      fills"; it converges in at most R+1 rounds (each round pins at
      least the earliest not-yet-recorded fill event) and typically one.
      This replaces an n_pad-iteration sequential scan per slot with a
      handful of cumsums — the difference between ~9 ms and ~0.4 ms per
      slot at n_pad=768.
    - ``uniform=False``: the literal sequential row walk (a later small-k
      row may fit where an earlier big-k row did not, so fullness is not
      binary and the key-threshold model does not apply).
    """
    t = x["t"]
    rem = carry["remaining"]
    slack = carry["slack"]
    waited = carry["waited"]
    in_sys = carry["in_sys"]
    fin_all = carry["finished"]
    started = carry["started"]
    n_pad = rem.shape[0]
    i64 = jnp.int64

    arrived = consts["arrival"] <= t
    in_sys = in_sys | (arrived & ~fin_all)
    n_in = jnp.sum(in_sys)
    ended = carry["ended"] | ((n_in == 0)
                              & (jnp.sum(arrived) == consts["n_real"])
                              & (t >= consts["t_end"]))
    act = in_sys & ~ended

    forced = slack <= 0
    live = rem > _EPS
    cand = act & live & (carry["mig_left"] == 0)
    idx = jnp.arange(n_pad, dtype=i64)
    key = jnp.where(cand, (~forced).astype(i64) * n_pad + idx,
                    jnp.int64(2 * n_pad))

    if uniform:
        take, placed, polr, engr, migl, moves, mig_now = _geo_resolve_uniform(
            consts, carry, x, kind, lookahead, cand, forced, key, rem, slack,
            started)
    else:
        take, placed, polr, engr, migl, moves, mig_now = _geo_resolve_walk(
            consts, carry, x, kind, lookahead, cand, forced, key, rem, slack,
            started)

    rem2 = jnp.where(take, rem - consts["thr"], rem)
    started2 = started | take
    wmask = act & live & ~take
    slack2 = jnp.where(wmask, slack - 1, slack)
    waited2 = jnp.where(wmask, waited + 1, waited)
    migl2 = jnp.where(wmask & (migl > 0), migl - 1, migl)

    fin = act & (rem2 <= _EPS)
    viol = fin & (t > consts["deadline"])
    waited_fin = jnp.where(fin, waited2, 0)

    carry2 = dict(remaining=rem2, slack=slack2, waited=waited2,
                  in_sys=in_sys & ~fin, finished=fin_all | fin,
                  started=started2, placed=placed, pol_region=polr,
                  eng_region=engr, mig_left=migl2, moves=moves,
                  ended=ended)
    # lean device->host transfer: frac/k_vec/energy replay host-side from
    # the boolean take mask, region ids and counters fit int32
    ys = dict(take=take, region=engr.astype(jnp.int32),
              mig_now=mig_now, fin=fin, viol=viol,
              waited_fin=waited_fin.astype(jnp.int32),
              n_rows=n_in.astype(jnp.int32), ended=ended)
    return carry2, ys


def _geo_resolve_uniform(consts, carry, x, kind, lookahead, cand, forced,
                         key, rem, slack, started):
    """Vectorised uniform-k resolution: row-local placement preferences,
    migration economics and eligibility, then the fill-key fixpoint for
    the FCFS capacity coupling.  Bit-identical to the walk (same
    expressions evaluated per row; the only cross-row state — region
    fullness — is reproduced exactly by the fill keys)."""
    i64 = jnp.int64
    caps = consts["caps"]
    n_pad = rem.shape[0]
    n_r = caps.shape[0]
    ridx = jnp.arange(n_r, dtype=i64)
    strt = started

    # region bookkeeping before the capacity fixpoint (row-local)
    if kind == "geo-greedy":
        # defensive sync (policy: started & unplaced adopts a.region)
        adopt = cand & strt & ~carry["placed"]
        polr0 = jnp.where(adopt, carry["eng_region"], carry["pol_region"])
        placed0 = carry["placed"] | adopt
        rfix = polr0                    # walk's r for non-newly rows
    elif kind == "geo-flex":
        polr0 = carry["pol_region"]
        placed0 = carry["placed"]
        rfix = jnp.where(strt, carry["eng_region"], polr0)
    else:
        polr0 = carry["pol_region"]
        placed0 = carry["placed"]
        rfix = carry["eng_region"]

    # placement preference order (rows searching for a region)
    if kind == "geo-greedy":
        unplz = cand & ~strt & ~placed0
        pref = jnp.broadcast_to(x["clean_order"][None, :], (n_pad, n_r))
    elif kind == "geo-flex":
        unplz = cand & ~strt & ~placed0
        hp = jnp.minimum(jnp.float64(lookahead),
                         jnp.maximum(1.0, jnp.ceil(rem))).astype(i64)
        means_h = x["means"][:, jnp.clip(hp - 1, 0, lookahead - 1)].T
        pref = jnp.argsort(means_h, axis=1, stable=True)
    else:
        unplz = jnp.zeros_like(cand)
        pref = None

    # migration economics (row-local: greedy prices instantaneous CI,
    # flex prices forecast window means shifted past the migration window)
    if kind == "geo-static":
        do_mig = jnp.zeros_like(cand)
        best = rfix
        msv = jnp.zeros(n_pad, dtype=i64)
    else:
        msv = consts["mig_slots"]
        can = (cand & strt & (carry["moves"] < consts["max_moves"])
               & (slack > msv + 1) & (rem > msv.astype(jnp.float64)))
        if kind == "geo-greedy":
            h = jnp.maximum(1.0, jnp.ceil(rem))
            e_run = consts["ec"] * h
            stay = x["ci_now"][rfix] * e_run
            move = (x["ci_now"][None, :] * e_run[:, None]
                    + consts["mig_e"][:, None] * x["ci_now"][None, :])
        else:
            hm = jnp.minimum(
                (jnp.int64(lookahead) - msv).astype(jnp.float64),
                jnp.maximum(1.0, jnp.ceil(rem)))
            can = can & (hm >= 1.0)
            him = jnp.clip(hm.astype(i64) - 1, 0, lookahead - 1)
            e_run = consts["ec"] * hm
            stay = x["means"][rfix, him] * e_run
            move = (x["movemeans"][consts["mig_idx"][:, None],
                                   ridx[None, :], him[:, None]]
                    * e_run[:, None]
                    + consts["mig_e"][:, None] * x["ci_now"][None, :])
        move = jnp.where(ridx[None, :] == rfix[:, None], jnp.inf, move)
        best = jnp.argmin(move, axis=1)
        do_mig = can & (jnp.take_along_axis(move, best[:, None], 1)[:, 0]
                        < stay * consts["margin_c"])

    # --- fill-key fixpoint ---------------------------------------------------
    k0 = consts["kmin"][0]              # uniform k (real rows; row 0 is real)
    cap_n = caps // k0                  # takers each region can hold
    k_inf = jnp.int64(4 * n_pad)
    k_init = jnp.where(cap_n > 0, k_inf, jnp.int64(-1))
    fvalid = cand & ~do_mig             # rows that may consume capacity

    def decide(kfill):
        """Per-row target region + capacity/eligibility under fill keys."""
        if kind == "geo-static":
            return rfix, key <= kfill[rfix], jnp.ones_like(cand), \
                jnp.zeros_like(cand), rfix
        openp = key[:, None] <= kfill[pref]            # pref order
        first = jnp.argmax(openp, axis=1)
        any_open = jnp.any(openp, axis=1)
        t_pl = jnp.take_along_axis(pref, first[:, None], 1)[:, 0]
        target = jnp.where(unplz, t_pl, rfix)
        attempt = jnp.where(unplz, any_open, key <= kfill[rfix])
        if kind == "geo-flex":
            elig = forced | (x["ci_now"][target] <= x["thresh_eps"][target])
        else:
            elig = jnp.ones_like(cand)
        return target, attempt, elig, any_open, t_pl

    def refill(kfill):
        """One round: takers under current fill keys -> new fill keys.
        Taker counts in FCFS-key order without a sort: the key order is
        forced rows by index then unforced by index, so two cumsums give
        each taker's inclusive rank; the cap-th taker's key is the fill."""
        target, attempt, elig, _, _ = decide(kfill)
        m = fvalid & attempt & elig
        oh = m[:, None] & (target[:, None] == ridx[None, :])
        cf = jnp.cumsum(oh & forced[:, None], axis=0, dtype=i64)
        cu = jnp.cumsum(oh & ~forced[:, None], axis=0, dtype=i64)
        cnt = jnp.where(forced[:, None], cf, cf[-1][None, :] + cu)
        at_fill = oh & (cnt == cap_n[None, :])
        k_new = jnp.min(jnp.where(at_fill, key[:, None], k_inf), axis=0)
        return jnp.minimum(kfill, k_new)

    k1 = refill(k_init)
    kfill, _ = lax.while_loop(
        lambda st: st[1],
        lambda st: (lambda k2: (k2, jnp.any(k2 != st[0])))(refill(st[0])),
        (k1, jnp.any(k1 != k_init)))

    target, attempt, elig, any_open, t_pl = decide(kfill)
    take = fvalid & attempt & elig
    if kind == "geo-static":
        return (take, carry["placed"], carry["pol_region"],
                carry["eng_region"], carry["mig_left"], carry["moves"],
                jnp.zeros_like(cand))
    newly = unplz & any_open            # placed even when ineligible to run
    placed = placed0 | newly | do_mig
    polr = jnp.where(do_mig, best, jnp.where(newly, t_pl, polr0))
    # engine region: migration moves it; a granted allocation on a
    # never-started job is a free placement
    engr = jnp.where(do_mig, best,
                     jnp.where(take & ~strt, target, carry["eng_region"]))
    migl = jnp.where(do_mig, msv, carry["mig_left"])
    moves = carry["moves"] + do_mig.astype(i64)
    return take, placed, polr, engr, migl, moves, do_mig


def _geo_resolve_walk(consts, carry, x, kind, lookahead, cand, forced, key,
                      rem, slack, started):
    """Literal sequential FCFS walk (non-uniform ``k_min`` fallback)."""
    i64 = jnp.int64
    caps = consts["caps"]
    kmin = consts["kmin"]
    n_pad = rem.shape[0]
    order = jnp.argsort(key, stable=True)

    def walk(st, row):
        used, placed, polr, engr, migl, moves, take, mig_now = st
        valid = cand[row]
        k = kmin[row]
        rv = rem[row]
        strt = started[row]

        if kind == "geo-static":
            r = engr[row]
            newly = jnp.asarray(False)
            r_new = r
        elif kind == "geo-greedy":
            # defensive sync (policy: started & unplaced adopts a.region)
            adopt = valid & strt & ~placed[row]
            polr0 = jnp.where(adopt, engr[row], polr[row])
            placed0 = placed[row] | adopt
            co = x["clean_order"]
            fits_vec = used[co] + k <= caps[co]
            r_place = co[jnp.argmax(fits_vec)]
            newly = valid & ~strt & ~placed0 & jnp.any(fits_vec)
            r_new = jnp.where(newly, r_place, polr0)
            placed1 = placed0 | newly
            r = r_new
        else:  # geo-flex
            strt_r = engr[row]                  # started jobs: a.region
            hp = jnp.minimum(jnp.float64(lookahead),
                             jnp.maximum(1.0, jnp.ceil(rv))).astype(i64)
            means_h = x["means"][:, jnp.clip(hp - 1, 0, lookahead - 1)]
            porder = jnp.argsort(means_h, stable=True)
            fits_vec = used[porder] + k <= caps[porder]
            r_place = porder[jnp.argmax(fits_vec)]
            newly = valid & ~strt & ~placed[row] & jnp.any(fits_vec)
            placed1 = placed[row] | newly
            r_new = jnp.where(newly, r_place, polr[row])
            r = jnp.where(strt, strt_r, r_new)

        # migration economics (geo-greedy: instantaneous CI; geo-flex:
        # forecast window means shifted past the migration window)
        if kind == "geo-static":
            do_mig = jnp.asarray(False)
            best = r
            ms = jnp.int64(0)
        else:
            ms = consts["mig_slots"][row]
            can = (valid & strt & (moves[row] < consts["max_moves"])
                   & (slack[row] > ms + 1) & (rv > ms.astype(jnp.float64)))
            if kind == "geo-greedy":
                h = jnp.maximum(1.0, jnp.ceil(rv))
                e_run = consts["ec"][row] * h
                stay = x["ci_now"][r] * e_run
                mig_c = consts["mig_e"][row] * x["ci_now"]
                move = x["ci_now"] * e_run + mig_c
            else:
                hm = jnp.minimum((jnp.int64(lookahead) - ms).astype(
                    jnp.float64), jnp.maximum(1.0, jnp.ceil(rv)))
                can = can & (hm >= 1.0)
                hi = jnp.clip(hm.astype(i64) - 1, 0, lookahead - 1)
                e_run = consts["ec"][row] * hm
                stay = x["means"][r, hi] * e_run
                mig_c = consts["mig_e"][row] * x["ci_now"]
                move = (x["movemeans"][consts["mig_idx"][row], :, hi]
                        * e_run + mig_c)
            stay_m = stay * consts["margin_c"]
            move = move.at[r].set(jnp.inf)
            best = jnp.argmin(move)
            do_mig = can & (move[best] < stay_m)

        # run eligibility + capacity ("continue" on failure)
        if kind == "geo-flex":
            elig = forced[row] | (x["ci_now"][r] <= x["thresh_eps"][r])
        else:
            elig = jnp.asarray(True)
        placeable = (strt | placed[row] | newly) if kind != "geo-static" \
            else jnp.asarray(True)
        fits = used[r] + k <= caps[r]
        do_run = valid & ~do_mig & placeable & elig & fits

        used2 = used.at[r].add(jnp.where(do_run, k, 0))
        take2 = take.at[row].set(do_run)
        mig2 = mig_now.at[row].set(do_mig)
        if kind == "geo-static":
            placed2, polr2 = placed, polr
            engr2 = engr
        else:
            placed2 = placed.at[row].set(placed1 | do_mig)
            polr2 = polr.at[row].set(jnp.where(do_mig, best, r_new))
            # engine region: migration moves it; a granted allocation on a
            # never-started job is a free placement
            engr2 = engr.at[row].set(
                jnp.where(do_mig, best,
                          jnp.where(do_run & ~strt, r, engr[row])))
        migl2 = migl.at[row].set(jnp.where(do_mig, ms, migl[row]))
        moves2 = moves.at[row].add(do_mig.astype(i64))
        return (used2, placed2, polr2, engr2, migl2, moves2, take2,
                mig2), None

    st0 = (jnp.zeros(caps.shape[0], dtype=i64), carry["placed"],
           carry["pol_region"], carry["eng_region"], carry["mig_left"],
           carry["moves"], jnp.zeros(n_pad, dtype=bool),
           jnp.zeros(n_pad, dtype=bool))
    (used, placed, polr, engr, migl, moves, take, mig_now), _ = lax.scan(
        walk, st0, order)
    return take, placed, polr, engr, migl, moves, mig_now


@functools.partial(jax.jit, static_argnames=("kind", "lookahead", "uniform"))
def _geo_chunk(consts, carry, xs, kind: str, lookahead: int, uniform: bool):
    def step(c, x):
        return _geo_step(consts, c, x, kind=kind, lookahead=lookahead,
                         uniform=uniform)

    return lax.scan(step, carry, xs)


# --- host accounting ---------------------------------------------------------


def _active_energy(packed, power, slot_h, eta, take_a):
    """Replay fractional progress and the vector engine's exact energy
    expressions over the active (slot, row) cells of the emitted take
    mask, host-side.

    The device updates ``remaining`` with one subtraction per take slot
    (``rem - thr``) and derives ``frac = min(1, rem / thr_guard)`` from
    the pre-update value; replaying those row-wise here performs the
    identical scalar arithmetic in the identical order — bitwise equal —
    while keeping the device->host transfer to one boolean grid instead
    of an f64 one.  The nonzero cells (row-major: each slot's segment in
    row order) are the per-slot active sets.  Every energy operation is
    elementwise, so each cell sees the identical arithmetic to a
    per-slot replay (active cells have ``k >= 1``, so the ``maximum``
    divisor guard never fires).  Returns per-slot segment bounds plus
    row ids, allocations and energies of the active cells."""
    n = take_a.shape[1]
    s_idx, r_idx = np.nonzero(take_a)
    bounds = np.searchsorted(s_idx, np.arange(take_a.shape[0] + 1))
    thr = packed.thr_tab[np.arange(n), packed.k_min]
    thr_guard = np.maximum(thr, 1e-9)
    rem = packed.length.astype(np.float64, copy=True)
    frac = np.empty(len(r_idx))
    for i in range(take_a.shape[0]):
        rows = r_idx[bounds[i]:bounds[i + 1]]
        frac[bounds[i]:bounds[i + 1]] = np.minimum(
            1.0, rem[rows] / thr_guard[rows])
        rem[rows] -= thr[rows]
    k = packed.k_min[r_idx]
    e_comp = k * power[r_idx] * slot_h * frac
    ring = np.where(k <= 1, 0.0, 2.0 * (k - 1) / np.maximum(k, 1))
    gbits = packed.comm[r_idx] * 8.0 * ring * k * frac
    e = e_comp + eta * gbits / 3600.0 / 1000.0 * slot_h
    return bounds, r_idx, k, e


def _active_energy_cells(packed, power, slot_h, eta, take_a, k_rows):
    """``_active_energy`` for slot-varying allocations (mpc-scale).

    ``k_rows`` is the (S, n) grid of the allocation each take cell ran
    at; throughput is gathered per cell (``thr_tab[row, k]``) and the
    replay performs the identical per-slot scalar arithmetic the vector
    engine's allocated-k path does — bitwise equal by the same argument
    as the k_min replay above."""
    s_idx, r_idx = np.nonzero(take_a)
    bounds = np.searchsorted(s_idx, np.arange(take_a.shape[0] + 1))
    k = k_rows[s_idx, r_idx]
    thr = packed.thr_tab[r_idx, k]
    thr_guard = np.maximum(thr, 1e-9)
    rem = packed.length.astype(np.float64, copy=True)
    frac = np.empty(len(r_idx))
    for i in range(take_a.shape[0]):
        lo, hi = bounds[i], bounds[i + 1]
        rows = r_idx[lo:hi]
        frac[lo:hi] = np.minimum(1.0, rem[rows] / thr_guard[lo:hi])
        rem[rows] -= thr[lo:hi]
    e_comp = k * power[r_idx] * slot_h * frac
    ring = np.where(k <= 1, 0.0, 2.0 * (k - 1) / np.maximum(k, 1))
    gbits = packed.comm[r_idx] * 8.0 * ring * k * frac
    e = e_comp + eta * gbits / 3600.0 / 1000.0 * slot_h
    return bounds, r_idx, k, e


def _nbytes(tree) -> int:
    """Bytes held by the arrays of a pytree (host or device)."""
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))


def _collect_chunks(prog_consts, carry, chunk_fn, xs_fn, t0: int,
                    t_mid: int, t_hard: int,
                    prof: PhaseProfiler | None = None) -> tuple[dict, int]:
    """Run device chunks until the case ends or t_hard; returns stacked
    host ys + the count of valid (pre-termination) slots.

    Inside the horizon (< ``t_mid``) termination is impossible (the
    engines' ended-check requires ``t >= t0 + horizon``), so full CHUNK
    dispatches are free of waste; past the horizon the case can end any
    slot, so smaller OVERRUN_CHUNK dispatches bound the slots computed
    beyond the actual end.  With a profiler attached, each chunk's host
    tables, upload, device wait and fetch are spans of ``decide``, and
    the transfers and computed slots are counted."""
    ys_parts = []
    t_lo = t0
    while t_lo < t_hard:
        cap = CHUNK if t_lo < t_mid else OVERRUN_CHUNK
        size = min(cap, t_hard - t_lo)
        with span(prof, "decide/tables"):
            xs = xs_fn(np.arange(t_lo, t_lo + size))
        with span(prof, "decide/upload"):
            xs = jax.device_put(xs)
        with span(prof, "decide/wait"):
            carry, ys = chunk_fn(prog_consts, carry, xs)
            if prof is not None:
                jax.block_until_ready(ys)
        with span(prof, "decide/fetch"):
            ys = jax.device_get(ys)
            ended = bool(np.asarray(carry["ended"]))
        if prof is not None:
            prof.count("h2d_bytes", _nbytes(xs))
            prof.count("d2h_bytes", _nbytes(ys) + carry["ended"].nbytes)
        ys_parts.append(ys)
        t_lo += size
        if ended:
            break
    ys = {k: np.concatenate([p[k] for p in ys_parts]) for k in ys_parts[0]}
    ended = np.asarray(ys["ended"], dtype=bool)
    n_valid = int(np.argmax(ended)) if ended.any() else len(ended)
    if prof is not None:
        prof.count("scan_slots", len(ended))
        prof.count("scan_slots_past_end", len(ended) - n_valid)
    return ys, n_valid


def _run_single_native(packed, ci, ci_pol, cluster, policy, t0, horizon,
                       max_overrun, kind,
                       telemetry: Telemetry | None = None) -> SimResult:
    prof = telemetry.profiler if telemetry is not None else None
    with span(prof, "build"):
        prog = _build_single(packed, cluster, policy, ci_pol, kind, t0,
                             horizon, prof)
    t_hard = t0 + horizon + max_overrun

    def chunk_fn(consts, carry, xs):
        return _single_chunk(consts, carry, xs, prog.kind, prog.uniform,
                             prog.deps)

    with span(prof, "decide"):
        ys, n_valid = _collect_chunks(prog.consts, prog.carry0, chunk_fn,
                                      prog.xs_fn, t0, t0 + horizon, t_hard,
                                      prof)
    return _account_single(packed, ci, ci_pol, cluster, policy, t0, ys,
                           n_valid, prog, telemetry=telemetry)


def _scan_admit_slots(packed, t0, n_valid, fs, fr):
    """Reconstruct each row's admission slot from the finish grid.

    Mirrors the vector engine exactly: a row enters the system at
    ``max(arrival, t0)``, except DAG rows wait for every predecessor and
    release the slot *after* the last one finishes.  Rows whose
    predecessors never finish (or that admit past the run) return -1."""
    admit = np.maximum(packed.arrival, t0).astype(np.int64, copy=True)
    if packed.has_deps:
        comp = np.full(packed.n, -1, dtype=np.int64)
        comp[fr] = t0 + fs
        id2row = packed.id2row
        for r, job in enumerate(packed.jobs):
            for dep in job.deps:
                c = comp[id2row[dep]]
                if c < 0:
                    admit[r] = -1
                    break
                admit[r] = max(admit[r], c + 1)
    admit[admit - t0 >= n_valid] = -1
    return admit


def _scan_slot_events(take, fs, fr, n_valid):
    """Vectorised resume/suspend derivation from the dense take grid.

    Semantically identical to feeding ``SlotEventTracker.step`` the
    per-slot allocation stream (the native scan k is always ``k_min``,
    so scale events cannot fire), but computed in a handful of whole-run
    numpy passes instead of a per-slot Python walk — this is what keeps
    scan-path recording inside its 1.3x overhead budget.  Returns
    ``(resume_rows, resume_bounds, suspend_rows, suspend_bounds)`` with
    rows ascending within each slot (scan packing sorts rows by job id,
    so ascending row order == the tracker's sorted-job suspend order).
    """
    m = np.asarray(take, dtype=bool)
    n = m.shape[1]
    # on/off transitions between consecutive slots (transition index i is
    # slot i+1); slot 0 has no transitions — first activations there are
    # starts, and nothing can switch off into it.
    cs, cr = np.nonzero(m[1:] & ~m[:-1])
    # a row's first switch-on is its start (admit covers it), unless the
    # row was already running at slot 0 — then every switch-on resumes.
    uniq, first = np.unique(cr, return_index=True)
    keep = np.ones(len(cr), dtype=bool)
    keep[first[~m[0][uniq]]] = False
    rs, rr = cs[keep] + 1, cr[keep]
    # a switch-off is a suspend unless the row finished at the prior slot
    # (each row finishes at most once, so a per-row slot table suffices)
    os_, orow = np.nonzero(m[:-1] & ~m[1:])
    finslot = np.full(n, -2, dtype=np.int64)
    if len(fs):
        finslot[np.asarray(fr)] = fs
    keep = os_ != finslot[orow]
    ss, sr = os_[keep] + 1, orow[keep]
    return (rr.tolist(), np.searchsorted(rs, np.arange(n_valid + 1)),
            sr.tolist(), np.searchsorted(ss, np.arange(n_valid + 1)))


def _account_single(packed, ci, ci_pol, cluster, policy, t0, ys, n_valid,
                    prog, telemetry: Telemetry | None = None) -> SimResult:
    from .simulator import _run_resilience, _telemetry_hooks

    tele, prof, _, _ = _telemetry_hooks(telemetry, None)
    n = packed.n
    slot_h = cluster.slot_hours
    eta = cluster.eta_net
    wait = np.zeros(n)
    violations = np.zeros(n, dtype=bool)
    completion = np.full(n, -1, dtype=np.int64)
    logs: list[SlotLog] = []
    total_energy = 0.0
    total_carbon = 0.0
    take_a = ys["take"][:n_valid, :n]
    if prog.kind == "mpc-scale":
        k_rows = np.where(np.asarray(ys["scaled"][:n_valid, :n], dtype=bool),
                          prog.k_up[None, :], packed.k_min[None, :])
        bounds, r_idx, k_act, e_act = _active_energy_cells(
            packed, prog.power, slot_h, eta, take_a, k_rows)
    else:
        bounds, r_idx, k_act, e_act = _active_energy(packed, prog.power,
                                                     slot_h, eta, take_a)
    fs, fr = np.nonzero(ys["fin"][:n_valid, :n])
    fbounds = np.searchsorted(fs, np.arange(n_valid + 1))
    wfin_f = ys["waited_fin"][:n_valid, :n][fs, fr]
    viol_f = ys["viol"][:n_valid, :n][fs, fr]
    n_rows_a = ys["n_rows"][:n_valid]
    civ_a = _ci_block(ci, t0, n_valid)
    admits_by: dict[int, list[int]] = {}
    if tele is not None:
        aslots = _scan_admit_slots(packed, t0, n_valid, fs, fr)
        for r, s in enumerate(aslots.tolist()):     # row order == sorted
            if s >= 0:
                admits_by.setdefault(s, []).append(r)
        jids = packed.job_ids.tolist()
        kv = [float(k) for k in packed.k_min.tolist()]
        rr, rb, sr, sb = _scan_slot_events(take_a, fs, fr, n_valid)
        emit = tele.emit
    with span(prof, "execute"):
        for i in range(n_valid):
            t = t0 + i
            civ = float(civ_a[i])
            lo, hi = bounds[i], bounds[i + 1]
            if tele is not None:
                for r in admits_by.get(t, ()):
                    emit(t, "admit", job=jids[r])
                if ci_pol is not ci:
                    emit(t, "forecast-read", value=float(ci_pol.staleness(t)))
                for r in rr[rb[i]:rb[i + 1]]:
                    emit(t, "resume", job=jids[r], value=kv[r])
                for r in sr[sb[i]:sb[i + 1]]:
                    emit(t, "suspend", job=jids[r])
            energy = 0.0
            for v in e_act[lo:hi].tolist():    # sequential sum, scalar order
                energy += v
            carbon = emissions.slot_carbon_g(energy, civ)
            total_energy += energy
            total_carbon += carbon
            flo, fhi = fbounds[i], fbounds[i + 1]
            frows = fr[flo:fhi]
            if len(frows):
                completion[frows] = t
                wait[frows] = wfin_f[flo:fhi]
                violations[frows] = viol_f[flo:fhi]
            used = int(k_act[lo:hi].sum())
            running = int(hi - lo)
            logs.append(SlotLog(slot=t, ci=civ, provisioned=prog.m_t,
                                used=used, energy_kwh=energy, carbon_g=carbon,
                                running=running,
                                queued=int(n_rows_a[i]) - len(frows)
                                - running))
    return SimResult(
        policy=policy.name, carbon_g=total_carbon, energy_kwh=total_energy,
        slots=logs, wait_slots=wait, violations=violations,
        completion=completion, num_jobs=n,
        resilience=_run_resilience(None, ci_pol, ci, t0, t0 + n_valid))


def _run_geo_native(packed, mci, ci_pol, geo, policy, t0, horizon,
                    max_overrun, kind,
                    telemetry: Telemetry | None = None) -> SimResult:
    from .simulator import (_accumulate_regions, _run_resilience,
                            _telemetry_hooks)

    tele, prof, _, _ = _telemetry_hooks(telemetry, None)
    lookahead = int(getattr(policy, "lookahead", 24))
    t_hard = t0 + horizon + max_overrun
    with span(prof, "build"):
        prog = _build_geo(packed, geo, policy, ci_pol, t0, horizon, kind,
                          prof)

    def chunk_fn(consts, carry, xs):
        return _geo_chunk(consts, carry, xs, kind, lookahead, prog.uniform)

    with span(prof, "decide"):
        ys, n_valid = _collect_chunks(prog.consts, prog.carry0, chunk_fn,
                                      prog.xs_fn, t0, t0 + horizon, t_hard,
                                      prof)

    n = packed.n
    n_regions = geo.n_regions
    slot_h = geo.slot_hours
    eta = geo.eta_net
    wait = np.zeros(n)
    violations = np.zeros(n, dtype=bool)
    completion = np.full(n, -1, dtype=np.int64)
    final_region = np.full(n, -1, dtype=np.int64)
    region_energy = np.zeros(n_regions)
    region_carbon = np.zeros(n_regions)
    migrations = 0
    mig_carbon_total = 0.0
    logs: list[SlotLog] = []
    total_energy = 0.0
    total_carbon = 0.0
    provisioned = int(prog.caps.sum())
    take_a = ys["take"][:n_valid, :n]
    reg_a = ys["region"][:n_valid, :n]
    bounds, r_act, k_act, e_act = _active_energy(packed, prog.power, slot_h,
                                                 eta, take_a)
    areg_act = reg_a[np.repeat(np.arange(n_valid), np.diff(bounds)), r_act]
    fs, fr = np.nonzero(ys["fin"][:n_valid, :n])
    fbounds = np.searchsorted(fs, np.arange(n_valid + 1))
    wfin_f = ys["waited_fin"][:n_valid, :n][fs, fr]
    viol_f = ys["viol"][:n_valid, :n][fs, fr]
    ms_idx, mr_idx = np.nonzero(ys["mig_now"][:n_valid, :n])
    mbounds = np.searchsorted(ms_idx, np.arange(n_valid + 1))
    n_rows_a = ys["n_rows"][:n_valid]
    civ_a = _ci_vec_acct_block(mci, t0, n_valid)
    admits_by: dict[int, list[int]] = {}
    if tele is not None:
        # geo native excludes DAG jobs, so admission is arrival-only
        aslots = _scan_admit_slots(packed, t0, n_valid, (), ())
        for r, s in enumerate(aslots.tolist()):     # row order == sorted
            if s >= 0:
                admits_by.setdefault(s, []).append(r)
        jids = packed.job_ids.tolist()
        kv = [float(k) for k in packed.k_min.tolist()]
        rr, rb, sr, sb = _scan_slot_events(take_a, fs, fr, n_valid)
        emit = tele.emit
    with span(prof, "execute"):
        for i in range(n_valid):
            t = t0 + i
            ci_vec = civ_a[i]
            lo, hi = bounds[i], bounds[i + 1]
            mrows = mr_idx[mbounds[i]:mbounds[i + 1]]
            if tele is not None:
                for r in admits_by.get(t, ()):
                    emit(t, "admit", job=jids[r])
                if ci_pol is not mci:
                    emit(t, "forecast-read", value=float(ci_pol.staleness(t)))
                for row in mrows.tolist():             # decision order
                    src = (int(reg_a[i - 1, row]) if i > 0
                           else geo.home_region(row))
                    emit(t, "migrate", job=jids[row],
                         value=float(reg_a[i, row]), detail=f"from={src}")
                for r in rr[rb[i]:rb[i + 1]]:
                    emit(t, "resume", job=jids[r], value=kv[r])
                for r in sr[sb[i]:sb[i + 1]]:
                    emit(t, "suspend", job=jids[r])
            e_vec = e_act[lo:hi]
            a_regions = areg_act[lo:hi]
            energy_r = np.zeros(n_regions)
            for r in range(n_regions):
                for v in e_vec[a_regions == r].tolist():
                    energy_r[r] += v
            mc = 0.0
            for row in mrows.tolist():         # row order == decision order
                e = prog.mig_e[row]
                dest = int(reg_a[i, row])
                energy_r[dest] += e
                mc += e * ci_vec[dest]
            mig_carbon_total += mc
            migrations += len(mrows)
            energy, carbon = _accumulate_regions(energy_r, ci_vec,
                                                 region_energy, region_carbon)
            total_energy += energy
            total_carbon += carbon
            flo, fhi = fbounds[i], fbounds[i + 1]
            frows = fr[flo:fhi]
            if len(frows):
                completion[frows] = t
                wait[frows] = wfin_f[flo:fhi]
                violations[frows] = viol_f[flo:fhi]
                final_region[frows] = reg_a[i, frows]
            used = int(k_act[lo:hi].sum())
            running = int(hi - lo)
            logs.append(SlotLog(slot=t, ci=float(np.mean(ci_vec)),
                                provisioned=provisioned, used=used,
                                energy_kwh=energy, carbon_g=carbon,
                                running=running,
                                queued=int(n_rows_a[i]) - len(frows)
                                - running))
    return SimResult(
        policy=policy.name, carbon_g=total_carbon, energy_kwh=total_energy,
        slots=logs, wait_slots=wait, violations=violations,
        completion=completion, num_jobs=n, regions=geo.regions,
        region_carbon_g=region_carbon, region_energy_kwh=region_energy,
        final_region=final_region, migrations=migrations,
        migration_carbon_g=mig_carbon_total,
        resilience=_run_resilience(None, ci_pol, mci, t0, t0 + n_valid))


# --- public API --------------------------------------------------------------


def simulate_scan(jobs, ci, cluster, policy, t0: int = 0,
                  horizon: int | None = None, max_overrun: int = 24 * 21,
                  faults=None, packed=None,
                  telemetry: Telemetry | None = None) -> SimResult:
    """``simulate(..., engine="scan")``: jitted lax.scan slot loop for
    native policies, transparent vector-engine delegation otherwise."""
    from .simulator import (_packed_for, _policy_ci_view, _simulate_vector,
                            _simulate_geo_vector)

    prof = telemetry.profiler if telemetry is not None else None
    if packed is None:
        with span(prof, "pack"):
            packed = _packed_for(jobs, prof)
    kind = native_kind(policy, cluster, faults)
    if (kind == "mpc-scale" and telemetry is not None
            and telemetry.recorder is not None):
        # _scan_slot_events derives resume/suspend assuming k == k_min
        # (no scale events); event-recorded scale runs use the vector
        # engine, whose tracker sees the true per-slot allocations
        kind = None
    if kind is None or packed.n == 0 or (packed.has_deps
                                         and isinstance(cluster, GeoCluster)):
        if isinstance(cluster, GeoCluster):
            # geo + deps delegates so the vector engine raises its usual
            # "geo engines do not support DAG jobs" rejection
            return _simulate_geo_vector(jobs, ci, cluster, policy, t0,
                                        horizon, max_overrun, faults,
                                        packed=packed, telemetry=telemetry)
        return _simulate_vector(jobs, ci, cluster, policy, t0, horizon,
                                max_overrun, faults, packed=packed,
                                telemetry=telemetry)
    horizon = int(horizon if horizon is not None else len(ci) - t0)
    ci_pol = _policy_ci_view(ci)
    with span(prof, "policy_tables"):
        policy.on_window_start(ci_pol, t0, horizon, packed.jobs, cluster)
    with jax.enable_x64(True):
        if kind in _SINGLE_KINDS:
            return _run_single_native(packed, ci, ci_pol, cluster, policy,
                                      t0, horizon, max_overrun, kind,
                                      telemetry=telemetry)
        return _run_geo_native(packed, ci, ci_pol, cluster, policy, t0,
                               horizon, max_overrun, kind,
                               telemetry=telemetry)


def simulate_many_scan(cases: Sequence) -> list[SimResult]:
    """Batch path: group scan-native single-region cases by structure and
    run each group as one vmapped device program (chunked); geo-native
    cases run per-case through the jitted geo scan; everything else
    delegates to the vector engine."""
    from .simulator import (_packed_for, _policy_ci_view, _simulate_vector,
                            _simulate_geo_vector)

    results: list[SimResult | None] = [None] * len(cases)
    groups: dict[tuple, list[tuple[int, object, object, _SingleProgram]]] = {}
    delegated: dict[str, int] = {}
    with jax.enable_x64(True):
        for i, case in enumerate(cases):
            telemetry = getattr(case, "telemetry", None)
            prof = telemetry.profiler if telemetry is not None else None
            with span(prof, "pack"):
                packed = _packed_for(case.jobs, prof)
            kind = native_kind(case.policy, case.cluster, case.faults)
            if (kind == "mpc-scale" and telemetry is not None
                    and telemetry.recorder is not None):
                kind = None     # see simulate_scan: scale events
            if kind is None or packed.n == 0 or (
                    packed.has_deps and isinstance(case.cluster, GeoCluster)):
                if packed.n > 0:
                    who = (getattr(case, "label", "")
                           or type(case.policy).__name__)
                    delegated[who] = delegated.get(who, 0) + 1
                fn = (_simulate_geo_vector
                      if isinstance(case.cluster, GeoCluster)
                      else _simulate_vector)
                results[i] = fn(case.jobs, case.ci, case.cluster,
                                case.policy, case.t0, case.horizon,
                                case.max_overrun, case.faults, packed=packed,
                                telemetry=telemetry)
                continue
            horizon = int(case.horizon if case.horizon is not None
                          else len(case.ci) - case.t0)
            ci_pol = _policy_ci_view(case.ci)
            with span(prof, "policy_tables"):
                case.policy.on_window_start(ci_pol, case.t0, horizon,
                                            packed.jobs, case.cluster)
            if kind not in _SINGLE_KINDS:
                results[i] = _run_geo_native(packed, case.ci, ci_pol,
                                             case.cluster, case.policy,
                                             case.t0, horizon,
                                             case.max_overrun, kind,
                                             telemetry=telemetry)
                continue
            with span(prof, "build"):
                prog = _build_single(packed, case.cluster, case.policy,
                                     ci_pol, kind, case.t0, horizon, prof)
            dep_dim = (prog.consts["pred_rows"].shape[1]
                       if prog.deps == "gather"
                       else prog.consts["parents"].shape[0]
                       if prog.deps == "scatter" else 0)
            key = (prog.n_pad, prog.kind, prog.xs_dims, prog.deps,
                   int(dep_dim), prog.uniform, horizon,
                   horizon + case.max_overrun)
            groups.setdefault(key, []).append((i, case, packed, prog, ci_pol))
        for key, members in groups.items():
            for lo in range(0, len(members), BATCH_TILE):
                _run_single_tile(members[lo:lo + BATCH_TILE], results)
    if delegated:
        # once per batch, not per case: sweeps that think they run on the
        # scan engine should know which cases silently fell back
        _log.info("scan batch: %d case(s) delegated to the vector engine "
                  "(%s)", sum(delegated.values()),
                  ", ".join(f"{k} x{v}" for k, v in sorted(delegated.items())))
    return results  # type: ignore[return-value]


def _run_single_tile(members, results) -> None:
    """One vmapped tile of structurally identical single-region cases."""
    if len(members) == 1:
        i, case, packed, prog, ci_pol = members[0]
        horizon = int(case.horizon if case.horizon is not None
                      else len(case.ci) - case.t0)
        t_hard = case.t0 + horizon + case.max_overrun

        def chunk_fn(consts, carry, xs):
            return _single_chunk(consts, carry, xs, prog.kind, prog.uniform,
                                 prog.deps)

        telemetry = getattr(case, "telemetry", None)
        prof = telemetry.profiler if telemetry is not None else None
        with span(prof, "decide"):
            ys, n_valid = _collect_chunks(prog.consts, prog.carry0, chunk_fn,
                                          prog.xs_fn, case.t0,
                                          case.t0 + horizon, t_hard, prof)
        results[i] = _account_single(packed, case.ci, ci_pol, case.cluster,
                                     case.policy, case.t0, ys, n_valid, prog,
                                     telemetry=telemetry)
        return

    tels = [getattr(m[1], "telemetry", None) for m in members]
    profs = [t.profiler if t is not None else None for t in tels]
    # a sweep's cells share one profiler: the tile is then one bracket of
    # it, with the spans of each chunk; otherwise no spans, and the
    # members split the tile's decide time evenly
    prof = profs[0] if all(p is profs[0] for p in profs) else None
    watched = any(p is not None for p in profs)
    kind_b = members[0][3].kind
    uniform = members[0][3].uniform
    deps = members[0][3].deps
    with span(prof, "build"):
        consts = {k: jnp.stack([m[3].consts[k] for m in members])
                  for k in members[0][3].consts}
        carry = {k: jnp.stack([m[3].carry0[k] for m in members])
                 for k in members[0][3].carry0}
    horizon_b = int(members[0][1].horizon
                    if members[0][1].horizon is not None
                    else len(members[0][1].ci) - members[0][1].t0)
    t_span = members[0][1].max_overrun + horizon_b
    ys_parts = []
    off = h2d = d2h = 0
    _dev_t0 = time.perf_counter()
    with span(prof, "decide"):
        while off < t_span:
            size = min(CHUNK if off < horizon_b else OVERRUN_CHUNK,
                       t_span - off)
            with span(prof, "decide/tables"):
                xs_host = [m[3].xs_fn(np.arange(m[1].t0 + off,
                                                m[1].t0 + off + size))
                           for m in members]
                xs = {k: np.stack([d[k] for d in xs_host])
                      for k in xs_host[0]}
            with span(prof, "decide/upload"):
                xs = {k: jnp.asarray(v) for k, v in xs.items()}
            with span(prof, "decide/wait"):
                carry, ys = _single_chunk_batch(consts, carry, xs, kind_b,
                                                uniform, deps)
                if prof is not None:
                    jax.block_until_ready(ys)
            with span(prof, "decide/fetch"):
                ys = jax.device_get(ys)
                ended = bool(np.asarray(carry["ended"]).all())
            if watched:
                h2d += _nbytes(xs)
                d2h += _nbytes(ys) + carry["ended"].nbytes
            ys_parts.append(ys)
            off += size
            if ended:
                break
    if prof is None:
        _dev_dt = (time.perf_counter() - _dev_t0) / len(members)
        for p in profs:
            if p is not None:
                p.add("decide", _dev_dt)
    ys_all = {k: np.concatenate([p[k] for p in ys_parts], axis=1)
              for k in ys_parts[0]}
    for j, (i, case, packed, prog, ci_pol) in enumerate(members):
        ys = {k: v[j] for k, v in ys_all.items()}
        ended = np.asarray(ys["ended"], dtype=bool)
        n_valid = int(np.argmax(ended)) if ended.any() else len(ended)
        if profs[j] is not None:
            # every member computed every slot the tile ran; the stacked
            # transfers divide evenly (members share their shapes)
            profs[j].count("scan_slots", off)
            profs[j].count("scan_slots_past_end", off - n_valid)
            profs[j].count("h2d_bytes", h2d // len(members))
            profs[j].count("d2h_bytes", d2h // len(members))
        results[i] = _account_single(packed, case.ci, ci_pol, case.cluster,
                                     case.policy, case.t0, ys, n_valid, prog,
                                     telemetry=tels[j])
