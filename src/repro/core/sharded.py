"""Multi-lane sharded priority queue: lane-native APEX-Q lanes (MultiQueues).

Scaling axis beyond one combined tick: L independent :mod:`pqueue` lanes
ticked together in ONE synchronized round.  Only the unconditional tick
head runs under ``jax.vmap``; every data-dependent pass (combine,
scatter, rebalance, moveHead, chopHead) has its predicate reduced
ACROSS lanes and runs lane-major — all lanes through one leading-axis
kernel call — under a batch-level ``lax.cond`` that fires only when
some lane needs it (DESIGN.md §6.1: ``vmap`` lowers ``lax.cond`` to
``select``, which would make every lane pay every rare path on every
tick).  Semantics follow
the relaxed priority queues of Rihani, Sanders & Dementiev 2014
("MultiQueues: Simpler, Faster, and Better Relaxed Concurrent Priority
Queues") combined with the explicit-synchronization batching of Aksenov &
Kuznetsov's Parallel Combining — each tick is one synchronized round over
all lanes:

* **pre-route elimination** (paper §2.2 at queue level): before the
  router runs, the tick's adds are matched 1:1 against its removeMin
  allocation under the min-of-lane-heads safety bound — on balanced
  mixes a matched pair is served directly and never pays routing, a
  lane tick, or grant allocation.  An adaptive gate (EMA of hit rate
  and add/remove balance, carried in :class:`ShardedState`) runs the
  pass under one batch-level ``lax.cond`` so unbalanced workloads pay a
  single pass-through conditional; see :func:`_preroute_eliminate`.
* **adds** go through a *stick-random router*: each batch slot is
  assigned a lane by a PRNG permutation of the round-robin pattern
  ``slot % L`` that is held fixed ("sticks") for ``stick`` ticks before
  resampling.  Sticking amortizes routing state and models MultiQueues'
  thread-local queue affinity; permuting a balanced pattern (instead of
  i.i.d. draws) caps any lane's share of a batch at ``ceil(W / L)`` by
  construction, so ceil(W/L)-sized lane quotas can never drop an add,
  while the randomness still decorrelates lanes from key order — which
  is what bounds the rank error of removals.
* **removes** use a *c-relaxed min-of-lane-heads* policy: the batch of r
  removeMin() ops is split evenly across lanes (each lane serves its own
  exact minima), with the remainder and any shortfall redistribution
  granted in order of the lanes' current head keys (smallest
  ``min_value`` first).  Each removed key is exact for its lane; relative
  to the union state a removed key can be displaced from the true minima
  by at most the elements the *other* lanes served past it, giving the
  MultiQueues-style guarantee that every removed key lies within the
  ``c`` smallest of the union for ``c ~ r + O(L * r/L)`` under a balanced
  router (checked empirically by tests/test_sharded.py).

The structure is relaxed, not linearizable: ``tick`` returns *a* set of
near-minimal keys, trading exactness for an L-fold cut in per-lane batch
width (each lane's combine/sort/merge shapes shrink by ~L, the same lever
the paper pulls with elimination).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import elimination, obs, pqueue
from repro.core.config import EMPTY_VAL, PQConfig
from repro.kernels import ops as kops
from repro.kernels.radix_select import _from_sortable_u32, _to_sortable_u32

INF = jnp.inf
_I32 = jnp.int32
_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class ShardedPQConfig:
    """Static config: `lane` is the per-lane PQConfig, `n_lanes` = L.

    ``lane.a_max``/``lane.r_max`` bound PER-LANE batch shares; the
    permuted round-robin router is balanced by construction, so
    ceil(width/L) quotas (slack 1.0 in make_sharded_cfg) can never
    overflow; if a caller under-sizes them anyway, overflowing adds are
    *dropped and counted* (n_router_dropped) rather than silently lost.
    """

    lane: PQConfig
    n_lanes: int = 4
    stick: int = 8          # ticks a routing permutation stays pinned
    a_total: int = 256      # un-sharded op-batch width fed to the router

    # --- pre-route elimination (paper §2.2 at queue level) ---------------
    # Before anything is routed, the tick's adds are matched 1:1 against
    # its removeMin allocation using the min-of-lane-heads as the safety
    # bound (see _preroute_eliminate).  `preroute` selects the gate:
    #   "adaptive" — a controller (EMA of hit rate + add/remove balance,
    #                carried in ShardedState) decides per tick under one
    #                batch-level lax.cond, with a periodic probe tick
    #                (every `elim_probe`, like the router's resample
    #                cadence) so a workload shift re-measures the rate;
    #   "on" / "off" — static forcing, used by the equivalence tests and
    #                the bench grid's disabled variant.
    preroute: str = "adaptive"
    elim_probe: int = 16        # probe cadence (ticks) of the adaptive gate
    elim_ema_decay: float = 0.25  # EMA step for both controller signals
    elim_gate: float = 0.25       # min EMA hit rate to keep the pass on
    balance_gate: float = 0.25    # min EMA min/max(add,rm) balance

    def __post_init__(self) -> None:
        if self.n_lanes < 1:
            raise ValueError("n_lanes must be >= 1")
        if self.stick < 1:
            raise ValueError("stick must be >= 1")
        if self.a_total < 1:
            raise ValueError("a_total must be >= 1")
        if self.preroute not in ("adaptive", "on", "off"):
            raise ValueError("preroute must be adaptive|on|off")
        if self.elim_probe < 1:
            raise ValueError("elim_probe must be >= 1")
        if not (0.0 < self.elim_ema_decay <= 1.0):
            raise ValueError("elim_ema_decay must be in (0, 1]")

    # duck-typed batch geometry so drivers written against PQConfig
    # (benchmarks/pq_bench.py) can treat a sharded queue as one wide queue
    @property
    def a_max(self) -> int:
        return self.a_total

    @property
    def r_max(self) -> int:
        return self.a_total


def _sharded_cfg(width: int, n_lanes: int, *, base: PQConfig,
                 slack: float = 1.0, min_lanes: int = None,
                 preroute: str = "adaptive") -> ShardedPQConfig:
    """Scale a width-`width` single-queue config down to L lanes.

    Per-lane batch geometry is ceil(slack * width / L) (clamped to
    [8, width]); structure capacities shrink by ~L.  slack defaults to
    1.0: the permuted round-robin router is balanced BY CONSTRUCTION —
    a lane appears exactly ceil(W/L) times in the route, so no mask can
    ever exceed the quota and extra slack would only widen every per-lane
    sort/merge/scatter shape (the lanes' whole advantage is that those
    shapes shrink by L; see DESIGN.md §6.1).  The sequential part gets
    the minimum legal headroom (2*per + 2): per-lane combine cost is
    dominated by the seq_cap + a_max merge, and a lane only ever needs
    its own share of head room, not base.seq_cap / L.

    ``min_lanes`` sizes the per-lane geometry for an ELASTIC queue that
    may fold down to that many lanes at runtime (:func:`fold_lanes` —
    the fault-tolerance path of core/distributed.py): quotas become
    ceil(width / min_lanes) — exact integer math, no float slack — so
    the balanced router still cannot overflow a lane after the fold.
    """
    eff = n_lanes if min_lanes is None else min_lanes
    if not (1 <= eff <= n_lanes):
        raise ValueError("min_lanes must be in [1, n_lanes]")
    per = max(8, min(width, max(int(-(-slack * width // n_lanes)),
                                -(-width // eff))))
    lane = dataclasses.replace(
        base,
        a_max=per, r_max=per,
        seq_cap=2 * per + 2,
        bucket_cap=max(base.bucket_cap // n_lanes, 8),
    )
    return ShardedPQConfig(lane=lane, n_lanes=n_lanes, a_total=width,
                           preroute=preroute)


def make_sharded_cfg(width: int, n_lanes: int, *, base: PQConfig,
                     slack: float = 1.0, min_lanes: int = None,
                     preroute: str = "adaptive") -> ShardedPQConfig:
    """Deprecated alias of the sharded config builder.

    Construction now goes through :func:`repro.core.factory.make_engine`
    (``EngineSpec(engine="sharded", ...)``), which resolves every engine
    kind behind one spec.  This alias survives for one PR so external
    callers keep working; in-repo callers have been migrated (enforced
    by tests/test_factory.py).
    """
    import warnings

    warnings.warn(
        "make_sharded_cfg is deprecated; use "
        "repro.core.factory.make_engine(EngineSpec(engine='sharded', ...))",
        DeprecationWarning, stacklevel=2)
    return _sharded_cfg(width, n_lanes, base=base, slack=slack,
                        min_lanes=min_lanes, preroute=preroute)


class ShardedState(NamedTuple):
    lanes: pqueue.PQState      # stacked pytree: every leaf has lead dim L
    rng: jnp.ndarray           # PRNG key for the router
    route: jnp.ndarray         # [a_max_total] current lane assignment
    route_inv: jnp.ndarray     # [a_max_total] argsort(route, stable): lane-
                               # grouped slot ids, refreshed with route —
                               # turns per-tick routing into static-segment
                               # gathers (the grouping sort happens once per
                               # resample, not once per tick)
    tick_idx: jnp.ndarray      # scalar i32 (drives re-sticking)
    n_router_dropped: jnp.ndarray   # adds dropped on lane-quota overflow
    # pre-route elimination controller (see ShardedPQConfig.preroute):
    elim_ema: jnp.ndarray      # scalar f32 EMA of the pass's hit rate,
                               # updated only on ticks where the pass ran
                               # with a nonzero pairing opportunity
    balance_ema: jnp.ndarray   # scalar f32 EMA of min/max(n_adds, rm)
    disp_ema: jnp.ndarray      # scalar f32 EMA of add-batch key dispersion
                               # (mean-min)/(max-min): ~1/ln(n) for the
                               # near-frontier exponential mixes where
                               # sharding wins, ~0.5 for uniform keys —
                               # the workload-controller signal that
                               # separates the two balanced regimes
                               # (core/adaptive.py reads it per window)
    n_preroute_elim: jnp.ndarray    # i32 pairs eliminated before routing
    n_preroute_ticks: jnp.ndarray   # i32 ticks where the pass ran


class ShardedTickResult(NamedTuple):
    """Compacted removal stream.  Width = max(a_total, n_lanes *
    lane.r_max) >= the a_total input batch (up to L * r_lane removals
    can be served)."""

    rm_keys: jnp.ndarray       # [out_w] f32, INF where unserved
    rm_vals: jnp.ndarray       # [out_w] i32
    rm_served: jnp.ndarray     # [out_w] bool


def _stack_init(cfg: ShardedPQConfig) -> pqueue.PQState:
    one = pqueue.init(cfg.lane)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (cfg.n_lanes,) + x.shape), one)


def init(cfg: ShardedPQConfig, *, seed: int = 0) -> ShardedState:
    # route placeholder only: tick 0 satisfies tick_idx % stick == 0, so
    # the first tick always resamples before routing anything
    return ShardedState(
        lanes=_stack_init(cfg),
        rng=jax.random.PRNGKey(seed),
        route=jnp.zeros((cfg.a_total,), _I32),
        route_inv=jnp.arange(cfg.a_total, dtype=_I32),
        tick_idx=jnp.zeros((), _I32),
        n_router_dropped=jnp.zeros((), _I32),
        # optimistic start: the pass runs until measured useless (tick 0
        # is also a probe tick, so the first mixed tick measures the rate)
        elim_ema=jnp.ones((), _F32),
        balance_ema=jnp.zeros((), _F32),
        # neutral start inside the controller's dead band: neither
        # regime is asserted until real add batches move the EMA
        disp_ema=jnp.full((), 0.27, _F32),
        n_preroute_elim=jnp.zeros((), _I32),
        n_preroute_ticks=jnp.zeros((), _I32),
    )


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

@jax.named_scope(obs.SQ_ROUTE)
def _fresh_route(key, w: int, n_lanes: int) -> jnp.ndarray:
    """Permuted round-robin lane map: balanced by construction (any batch
    window contains at most ceil(w / L) slots of one lane)."""
    return jax.random.permutation(
        key, jnp.arange(w, dtype=_I32) % n_lanes)


@jax.named_scope(obs.SQ_ROUTE)
def _route_adds(cfg: ShardedPQConfig, route, add_keys, add_vals, add_mask):
    """Distribute the add batch to per-lane [L, a_lane] arrays (slot
    order).

    One stable argsort by lane id groups each lane's elements into a
    contiguous segment of the batch; each lane then gathers its segment
    window (scatter-free, same trick as pqueue.scatter_parallel).
    Elements past a lane's a_max quota are dropped and counted.

    This is the REFERENCE router: the production tick uses
    :func:`_route_adds_sorted` (resample-amortized grouping + fused
    per-lane key sort); tests/test_tick_repairs.py routes through this
    one to pin the fused path against ``jax.vmap(pqueue.tick)``.
    """
    L, al = cfg.n_lanes, cfg.lane.a_max
    w = add_keys.shape[0]
    lane_of = jnp.where(add_mask, route, L)        # masked -> past the end
    order = jnp.argsort(lane_of, stable=True)      # [W], one batch sort
    sl = lane_of[order]
    sk = add_keys[order]
    sv = add_vals[order]
    lanes = jnp.arange(L, dtype=_I32)
    seg_start = jnp.searchsorted(sl, lanes, side="left").astype(_I32)
    seg_len = (jnp.searchsorted(sl, lanes, side="right").astype(_I32)
               - seg_start)
    slot = jnp.arange(al, dtype=_I32)[None, :]
    taken = slot < jnp.minimum(seg_len, al)[:, None]
    src = jnp.clip(seg_start[:, None] + slot, 0, w - 1)
    lk = jnp.where(taken, sk[src], INF)
    lv = jnp.where(taken, sv[src], EMPTY_VAL)
    n_in = add_mask.sum(dtype=_I32)
    n_routed = taken.sum(dtype=_I32)
    return lk, lv, taken, n_in - n_routed


def _route_geometry(w: int, n_lanes: int):
    """Static segment geometry of the balanced pattern ``arange(w) % L``:
    per-lane window indices into ``route_inv`` ([L, smax]) and the pad
    mask of slots past each lane's (static) segment length."""
    cnts = [(w + n_lanes - 1 - l) // n_lanes for l in range(n_lanes)]
    smax = max(cnts)
    offs, acc = [], 0
    for c in cnts:
        offs.append(acc)
        acc += c
    idx = (jnp.asarray(offs, _I32)[:, None]
           + jnp.arange(smax, dtype=_I32)[None, :])        # [L, smax]
    pad = jnp.arange(smax, dtype=_I32)[None, :] >= jnp.asarray(cnts,
                                                               _I32)[:, None]
    return idx, pad


@jax.named_scope(obs.SQ_ROUTE)
def _route_counts(cfg: ShardedPQConfig, route_inv, add_mask):
    """[L] live adds per lane under the current route — pure replicated
    math on the (replicated) route and mask, used by the distributed
    queue to compute grant `incoming` without waiting on routing."""
    w = add_mask.shape[0]
    idx, pad = _route_geometry(w, cfg.n_lanes)
    src = route_inv[jnp.clip(idx, 0, w - 1)]
    live = ~pad & add_mask[src]
    return jnp.sum(live, axis=-1, dtype=_I32)


@jax.named_scope(obs.SQ_ROUTE)
def _route_adds_sorted(cfg: ShardedPQConfig, route_inv, add_keys,
                       add_vals, add_mask, rows=None):
    """Fused router + per-lane sort via resample-amortized grouping.

    ``route_inv`` (stable argsort of the route, refreshed only when the
    route resamples) lists each lane's slots contiguously; because the
    route is a permutation of the balanced pattern ``slot % L``, every
    lane's segment size is STATIC (ceil/floor of W/L), so routing a
    tick's batch is one gather through static windows — no per-tick
    grouping sort.  One stable 2-operand ``lax.sort`` then key-sorts all
    lanes' rows in a single pass.  Within a lane ties keep slot order —
    bit-identical to routing first and letting each lane stably sort its
    own batch (what ``jax.vmap(pqueue.tick)`` computes; asserted by
    tests/test_tick_repairs.py).  Returns per-lane [L, a_lane] arrays
    ready for ``_tick_head(..., adds_sorted=True)``, plus the dropped
    count (elements past a lane's quota; zero at slack >= 1).

    ``rows=(lane_lo, n_rows)`` restricts the route/sort to a window of
    ``n_rows`` consecutive lanes starting at (traced) lane ``lane_lo``
    — each device of the distributed queue routes and sorts ONLY its
    own lanes' segments of the replicated batch.  Row results are
    identical to the full-batch call's rows (the per-row sort is
    row-independent), which is what keeps dist == single-device exact.
    """
    L, al = cfg.n_lanes, cfg.lane.a_max
    w = add_keys.shape[0]
    idx, pad = _route_geometry(w, L)                       # [L, smax]
    if rows is not None:
        lane_lo, n_rows = rows
        idx = jax.lax.dynamic_slice_in_dim(idx, lane_lo, n_rows, 0)
        pad = jax.lax.dynamic_slice_in_dim(pad, lane_lo, n_rows, 0)
    smax = idx.shape[1]
    src = route_inv[jnp.clip(idx, 0, w - 1)]               # [rows, smax]
    live = ~pad & add_mask[src]
    ck = jnp.where(live, add_keys[src].astype(_F32), INF)
    cv = jnp.where(live, add_vals[src].astype(_I32), EMPTY_VAL)
    su, sv = jax.lax.sort((_to_sortable_u32(ck), cv), num_keys=1,
                          is_stable=True)
    sk = _from_sortable_u32(su)
    n_lane = jnp.sum(live, axis=-1, dtype=_I32)
    if al >= smax:
        padw = al - smax
        lk = jnp.pad(sk, ((0, 0), (0, padw)), constant_values=INF)
        lv = jnp.pad(sv, ((0, 0), (0, padw)), constant_values=EMPTY_VAL)
        n_drop = jnp.zeros((), _I32)
    else:
        lk, lv = sk[:, :al], sv[:, :al]
        n_drop = jnp.sum(jnp.maximum(n_lane - al, 0), dtype=_I32)
    taken = jnp.arange(al, dtype=_I32)[None, :] < jnp.minimum(
        n_lane, al)[:, None]
    return lk, lv, taken, n_drop


@jax.named_scope(obs.SQ_GRANTS)
def _alloc_removes(cfg: ShardedPQConfig, lanes: pqueue.PQState, rm_count,
                   incoming=0):
    """c-relaxed min-of-lane-heads allocation of r removes to L lanes.

    Base share r // L each; the r % L remainder goes to the lanes with the
    smallest current heads; allocations past a lane's size are clawed back
    and re-granted to the remaining lanes in head order (one extra pass),
    which keeps total served = min(r, union size) whenever any single
    reallocation pass suffices (exact for the balanced loads the router
    produces; the property test drives skewed loads too).

    `incoming` is each lane's share of THIS tick's routed adds ([L] or
    0): a tick serves same-tick adds (elimination, merge prefix,
    moveHead all do), so a lane's serve capacity is pre-tick size +
    arrivals.  Clamping to the pre-tick size alone (the old behavior)
    silently left every lane a standing residue of one batch that could
    never drain — and kept every lane's combine/scatter/repair passes
    firing on every steady-state tick.
    """
    return _alloc_removes_arrays(
        cfg, lanes.seq_len + lanes.par_count, lanes.min_value, rm_count,
        incoming)


@jax.named_scope(obs.SQ_GRANTS)
def _alloc_removes_arrays(cfg: ShardedPQConfig, sizes_pre, min_value,
                          rm_count, incoming=0, grant_cap=None):
    """Array-level body of :func:`_alloc_removes`, taking the [L] lane
    summaries (pre-tick sizes and heads) directly instead of the stacked
    lane state — the distributed queue (core/distributed.py) feeds it
    ALL-GATHERED per-device lane vectors so every device computes the
    same replicated global allocation.

    ``grant_cap`` ([L] i32, optional) throttles per-lane grants below
    the r_max ceiling — the straggler degraded mode (repro.ft): a slow
    device's lanes get a smaller cap and the water-fill second pass
    re-grants the difference to healthy lanes in head order, so one
    straggler sheds serve work instead of stalling the synchronized
    round.  ``None`` (and any cap >= r_max) is bit-identical to the
    unthrottled allocation.
    """
    L = sizes_pre.shape[0]
    rl = cfg.lane.r_max
    if grant_cap is None:
        cap = jnp.full((L,), rl, _I32)
    else:
        cap = jnp.clip(jnp.asarray(grant_cap, _I32), 0, rl)
    sizes = sizes_pre + jnp.asarray(incoming, _I32)           # [L]
    heads = jnp.where(sizes > 0, min_value, INF)
    r = jnp.asarray(rm_count, _I32)
    base = r // L
    rem = r % L
    # rank by (head, lane id) via one [L, L] compare-all — identical to
    # argsort(argsort(heads)) but sort-free: three tiny sorts plus a
    # scatter sat on the tick's critical path (grants gate every lane's
    # head) and cost ~20x more than these L^2 compares
    i = jnp.arange(L, dtype=_I32)
    ahead = ((heads[None, :] < heads[:, None])
             | ((heads[None, :] == heads[:, None])
                & (i[None, :] < i[:, None])))
    head_rank = ahead.sum(axis=-1, dtype=_I32)
    want = base + (head_rank < rem).astype(_I32)
    grant = jnp.minimum(jnp.minimum(want, sizes), cap)
    shortfall = r - grant.sum(dtype=_I32)
    # second pass: hand the shortfall to lanes with leftover capacity,
    # again preferring small heads (water-fill by head order); a lane's
    # fill = whatever shortfall remains after all lanes ranked ahead of
    # it took their capacity
    cap_left = jnp.minimum(sizes, cap) - grant
    before = jnp.sum(
        jnp.where(head_rank[None, :] < head_rank[:, None],
                  cap_left[None, :], 0), axis=-1, dtype=_I32)
    extra = jnp.clip(jnp.minimum(cap_left, shortfall - before), 0, None)
    return grant + extra.astype(_I32)


# ---------------------------------------------------------------------------
# pre-route elimination (queue-level elimination array)
# ---------------------------------------------------------------------------

def _union_min(lanes: pqueue.PQState) -> jnp.ndarray:
    """min-of-lane-heads: the EXACT minimum of the pre-tick union.

    Each lane's ``min_value`` is exact for that lane (INF when empty), so
    the min over lanes is the union minimum — the safety bound of the
    pre-route pass.  Already replicated: it is a [L] reduction of state
    the tick reads anyway (``_alloc_removes`` ranks the same heads)."""
    return jnp.min(lanes.min_value)


@jax.named_scope(obs.SQ_PREROUTE)
def _preroute_eliminate(cfg: ShardedPQConfig, state: ShardedState,
                        add_keys, add_vals, add_mask, rm_count,
                        union_min=None):
    """Queue-level elimination BEFORE routing (paper §2.2 scaled to lanes).

    The paper's elimination array lets balanced add/removeMin traffic
    meet and cancel without ever touching the shared structure; the
    PR-2 queue only eliminated *inside* each lane after routing, so a
    matched pair still paid the router, a lane tick, and its grant.
    This pass matches the tick's adds against its removeMin allocation
    up front, bounded by the min-of-lane-heads: an add with
    ``key <= union_min`` is <= every key stored anywhere, so serving it
    straight to a removeMin is the strictest service any queue —
    relaxed or exact — could give (it cannot displace a smaller key,
    so the c-relaxation contract is untouched; DESIGN.md §6.2).
    Matched pairs never pay routing, lane ticks, or grant allocation.

    The gate (``cfg.preroute``):
      * "adaptive" — one batch-level ``lax.cond`` decides per tick from
        controller EMAs carried in ShardedState, so unbalanced
        workloads pay a single pass-through conditional.  The pass runs
        when the tick CAN pair (both adds and removes present) and
        either (a) this is a probe tick (every ``elim_probe`` ticks,
        the same amortization cadence as the router resample) or
        (b) both EMAs clear their gates — the balance EMA tracks
        min/max(adds, removes) (the paper's "similar numbers of add()
        and removeMin()" signal) and the hit-rate EMA tracks how much
        of the pairing opportunity recent passes actually matched.
      * "on"/"off" — static forcing; no cond is traced at all.

    Returns (residual add batch (k, v, mask), residual rm_count,
    matched_keys, matched_vals, n_matched, ran).  Residual adds keep
    their SLOT ORDER (matched slots' mask bits cleared) — the sortless
    variant of the elimination pass (`eliminate_batch_unsorted`): the
    paper licenses matching any eligible add, so no argsort of the
    a_total-wide batch sits on this hot path, and the stick router's
    slot-order quotas keep working untouched.
    """
    w = add_keys.shape[0]
    n_adds = add_mask.sum(dtype=_I32)
    opportunity = jnp.minimum(n_adds, rm_count)
    # the distributed queue overrides the bound with the GLOBAL
    # min-of-lane-heads (all-gathered across devices) so each device's
    # replicated pass matches against the same bound the single-device
    # queue would use
    if union_min is None:
        union_min = _union_min(state.lanes)

    def _run(_):
        er = elimination.eliminate_batch_unsorted(
            add_keys, add_vals, add_mask, rm_count, union_min)
        return (add_keys.astype(_F32), add_vals.astype(_I32),
                er.residual_mask, er.residual_rm, er.matched_keys,
                er.matched_vals, er.n_matched, jnp.ones((), bool))

    def _skip(_):
        return (add_keys.astype(_F32), add_vals.astype(_I32), add_mask,
                rm_count, jnp.full((w,), INF, _F32),
                jnp.full((w,), EMPTY_VAL, _I32), jnp.zeros((), _I32),
                jnp.zeros((), bool))

    if cfg.preroute == "off":
        return _skip(None)
    if cfg.preroute == "on":
        return _run(None)
    probe = (state.tick_idx % cfg.elim_probe) == 0
    gate = ((state.balance_ema >= cfg.balance_gate)
            & (state.elim_ema >= cfg.elim_gate))
    return jax.lax.cond((opportunity > 0) & (probe | gate), _run, _skip,
                        None)


def _dispersion(add_keys, add_mask):
    """Shape statistic of one tick's live add batch:
    ``(mean - min) / (max - min)`` — scale- and location-free, so it
    survives the drifting key frontier of DES streams.  Near-frontier
    exponential arrivals give ~1/ln(n) (~0.13 at bench widths), uniform
    keys ~0.5.  Returns ``(disp, informative)``: a tick with fewer than
    two distinct live keys carries no shape information."""
    m = add_mask
    n = m.sum(dtype=_I32)
    k = add_keys.astype(_F32)
    kmin = jnp.min(jnp.where(m, k, INF))
    kmax = jnp.max(jnp.where(m, k, -INF))
    mean = jnp.sum(jnp.where(m, k, 0.0)) / jnp.maximum(n, 1).astype(_F32)
    spread = kmax - kmin
    disp = (mean - kmin) / jnp.where(spread > 0, spread, 1.0)
    return disp, (n >= 2) & (spread > 0)


def _controller_update(cfg: ShardedPQConfig, state: ShardedState,
                       add_keys, add_mask, n_adds, rm_count, n_matched,
                       ran):
    """EMA bookkeeping for the adaptive gate and the workload
    controller (cheap scalar math, runs unconditionally — also under
    forced modes, so stats stay meaningful).  Each EMA only moves on
    ticks that carry information about its signal: the hit-rate EMA
    when the pass ran AND could have paired (opportunity > 0 — an
    add-only or remove-only tick says nothing about elimination yield),
    the balance EMA on any tick with ops at all (an IDLE tick says
    nothing about the add/remove mix — decaying on idle ticks would
    make bursty-but-balanced workloads look unbalanced and close the
    gate on exactly the ticks that could pair), and the dispersion EMA
    on ticks whose add batch has at least two distinct keys."""
    d = jnp.asarray(cfg.elim_ema_decay, _F32)
    opportunity = jnp.minimum(n_adds, rm_count)
    hit = n_matched.astype(_F32) / jnp.maximum(opportunity, 1).astype(_F32)
    elim_ema = jnp.where(ran & (opportunity > 0),
                         (1 - d) * state.elim_ema + d * hit,
                         state.elim_ema)
    peak = jnp.maximum(n_adds, rm_count)
    balance = opportunity.astype(_F32) / jnp.maximum(peak, 1).astype(_F32)
    balance_ema = jnp.where(peak > 0,
                            (1 - d) * state.balance_ema + d * balance,
                            state.balance_ema)
    disp, disp_ok = _dispersion(add_keys, add_mask)
    disp_ema = jnp.where(disp_ok, (1 - d) * state.disp_ema + d * disp,
                         state.disp_ema)
    return elim_ema, balance_ema, disp_ema


# ---------------------------------------------------------------------------
# the sharded tick
# ---------------------------------------------------------------------------

def _lanes_tick(lane_cfg, lanes: pqueue.PQState, lk, lv, lm, grants,
                *, adds_sorted: bool = False):
    """Fused lane-major tick over L stacked lanes.

    The repair-pass hoist (DESIGN.md §6.1): only the unconditional fast
    path runs under ``vmap`` (it contains no ``lax.cond``, so nothing is
    lowered to per-lane selects); each rare repair's predicate is then
    reduced ACROSS lanes and the repair runs lane-major — all lanes
    through one batched kernel call — under a single batch-level
    ``lax.cond`` that fires only when some lane needs it.  Lanes that did
    not ask for a firing repair keep their state bit-for-bit (per-lane
    select inside the repair), so the result is bit-identical to
    ``jax.vmap(pqueue.tick)`` (asserted by tests/test_tick_repairs.py)
    while a tick with no overflow/shortfall/quiet lane pays none of the
    flatten/extract/redistribute work ``vmap``'s cond→select lowering
    used to force on every lane every tick.

    Backend dispatch (the engine-level ``backend`` config): when
    ``lane_cfg.backend`` resolved to pallas, the whole hot pipeline —
    head, combine, scatter, predicates, AND the common moveHead repair —
    runs as ONE lanes-in-grid megakernel (kernels/lane_tick.py) instead
    of the vmap + hoisted-cond chain below; only the rare repairs and
    the finish stay out here.  Bit-identical either way (the megakernel
    equivalence leg of tests/test_lane_megakernel.py).
    """
    if lane_cfg.backend.is_pallas:
        return _lanes_tick_fused(lane_cfg, lanes, lk, lv, lm, grants,
                                 adds_sorted=adds_sorted)
    mid = jax.vmap(
        lambda s, k, v, m, r: pqueue._tick_head(
            lane_cfg, s, k, v, m, r, adds_sorted=adds_sorted),
    )(lanes, lk, lv, lm, grants)

    def _hoisted(pred, pass_fn, m):
        return jax.lax.cond(jnp.any(pred),
                            functools.partial(pass_fn, lane_cfg),
                            lambda x: x, m)

    # combine and scatter are hoisted too: on a drain tick whose batch
    # fully eliminates, no lane pays the seq_cap+a_max merge or the
    # bucket append at all.  The conds are NESTED under one outer
    # "anything to do?" cond, so a fully idle tick crosses a single
    # pass-through conditional — each cond boundary costs carry-buffer
    # traffic.  The outer predicate is a sound superset: chopHead needs
    # new_len > 0 (implies need_combine), rebalance needs a scatter, and
    # moveHead needs removes past the eliminated prefix plus a nonempty
    # (pre-tick or incoming) parallel part.
    def _active(m):
        m = _hoisted(m.pending.need_combine, pqueue._pass_combine, m)
        # need_scatter can only be RAISED by the combine pass (spill),
        # so re-reading it after the combine cond is what makes this
        # exact
        m = _hoisted(m.pending.need_scatter, pqueue._pass_scatter, m)
        m = pqueue._tick_preds(lane_cfg, m)

        p = m.pending
        for pred, repair in (
            (p.need_rebal & p.need_move, pqueue._repair_rebal_move),
            (p.need_rebal & ~p.need_move, pqueue._repair_rebalance),
            (p.need_move & ~p.need_rebal, pqueue._repair_move),
            (p.need_chop, pqueue._repair_chop),
        ):
            m = _hoisted(pred, repair, m)
        return m

    p = mid.pending
    may_move = ((mid.rm_count - mid.n_imm > 0)
                & (mid.par.par_count + mid.n_par_adds > 0))
    mid = jax.lax.cond(
        jnp.any(p.need_combine | p.need_scatter | may_move),
        _active, functools.partial(pqueue._tick_preds, lane_cfg), mid)
    state, res = pqueue._tick_finish(lane_cfg, mid)
    # per-lane served counts from the carry's counters (the removed
    # stream is a dense prefix per lane) — no array reduction needed
    n_lane = mid.pending.move_off + mid.n_rm_par
    return state, res, n_lane


def _lanes_tick_fused(lane_cfg, lanes, lk, lv, lm, grants, *,
                      adds_sorted: bool):
    """Pallas-backend twin of :func:`_lanes_tick`: the hot pipeline
    (including the moveHead repair, per-lane selected) is one
    lanes-in-grid ``pallas_call``; the three rare repairs keep exactly
    the jnp path's any-lane ``lax.cond`` hoists, and lanes a firing
    repair did not select keep their state bit-for-bit."""
    from repro.kernels import lane_tick as _lt   # lazy: import cycle
    mid = _lt.fused_tick_mid(lane_cfg, lanes, lk, lv, lm, grants,
                             adds_sorted=adds_sorted)
    p = mid.pending
    for pred, repair in (
        (p.need_rebal & p.need_move, pqueue._repair_rebal_move),
        (p.need_rebal & ~p.need_move, pqueue._repair_rebalance),
        (p.need_chop, pqueue._repair_chop),
    ):
        mid = jax.lax.cond(jnp.any(pred),
                           functools.partial(repair, lane_cfg),
                           lambda m: m, mid)
    state, res = pqueue._tick_finish(lane_cfg, mid)
    n_lane = mid.pending.move_off + mid.n_rm_par
    return state, res, n_lane


def _tick_impl(cfg: ShardedPQConfig, state: ShardedState, add_keys,
               add_vals, add_mask,
               rm_count) -> Tuple[ShardedState, ShardedTickResult]:
    L = cfg.n_lanes
    w = add_keys.shape[0]
    rl = cfg.lane.r_max
    out_w = max(w, cfg.n_lanes * rl)
    # the result stream can hold out_w serves; with the pre-route pass a
    # tick can serve matched pairs ON TOP of the lanes' L*r_max grants,
    # so the request is clamped to the stream width up front
    rm_count = jnp.minimum(jnp.asarray(rm_count, _I32), out_w)

    # -- pre-route elimination: match adds against the removeMin
    # allocation under the min-of-lane-heads bound; matched pairs are
    # served below as a prefix of the result stream and never reach the
    # router (gating: ShardedPQConfig.preroute / _preroute_eliminate) --
    n_adds_in = add_mask.sum(dtype=_I32)
    in_keys, in_mask = add_keys, add_mask   # pre-elimination batch: the
    # controller's dispersion signal reads the RAW arrival shape, not
    # the residual left after matched pairs were cancelled
    (add_keys, add_vals, add_mask, rm_residual, matched_k, matched_v,
     n_matched, elim_ran) = _preroute_eliminate(
        cfg, state, add_keys, add_vals, add_mask, rm_count)
    elim_ema, balance_ema, disp_ema = _controller_update(
        cfg, state, in_keys, in_mask, n_adds_in, rm_count, n_matched,
        elim_ran)

    # -- stick-random router refresh: the PRNG split, the permutation,
    # AND its stable inverse (the lane-grouped slot list) are all built
    # only under the resample branch.  The old code paid an
    # unconditional _fresh_route (a discarded [W] permutation 7 of
    # every 8 ticks at stick=8) and an unconditional jax.random.split —
    # whose threefry while-loops alone were a measurable per-tick cost
    # on CPU.  The rng therefore advances only on resample ticks. --
    resample = (state.tick_idx % cfg.stick) == 0

    @jax.named_scope(obs.SQ_ROUTE)
    def _resample(k):
        k2, sub = jax.random.split(k)
        fresh = _fresh_route(sub, w, L)
        return k2, fresh, jnp.argsort(fresh, stable=True).astype(_I32)

    key, route, route_inv = jax.lax.cond(
        resample, _resample,
        lambda k: (k, state.route, state.route_inv), state.rng)

    # -- lane-work hoist: a tick whose batch FULLY eliminated (or that
    # has no ops for nonempty lanes to serve) skips routing, grant
    # allocation, and the lane ticks behind one batch-level cond — this
    # is what makes "eliminated pairs never pay routing or lane ticks"
    # literal.  The skip is bit-exact: with zero routed adds and zero
    # grants a lane tick reduces to quiet_ticks++ and stats.n_ticks++
    # (the combine pass is an identity merge then, and no repair fires
    # — asserted against jax.vmap(pqueue.tick) by
    # tests/test_tick_repairs.py), EXCEPT when some quiet lane is about
    # to hit chop patience with a live head — those ticks take the full
    # path so chopHead fires exactly as the reference would --
    lc = cfg.lane
    n_res_adds = add_mask.sum(dtype=_I32)
    grants0 = _alloc_removes(cfg, state.lanes, rm_residual, incoming=0)
    quiet1 = state.lanes.quiet_ticks + 1
    any_chop = jnp.any((quiet1 >= lc.chop_patience)
                       & (state.lanes.seq_len > 0))
    lane_work = ((n_res_adds > 0) | (grants0.sum(dtype=_I32) > 0)
                 | any_chop)

    def _do(lanes_in):
        lk, lv, lm, n_drop = _route_adds_sorted(cfg, route_inv, add_keys,
                                                add_vals, add_mask)
        grants = _alloc_removes(cfg, lanes_in, rm_residual,
                                incoming=lm.sum(axis=-1, dtype=_I32))
        lanes2, res, n_lane = _lanes_tick(lc, lanes_in, lk, lv, lm,
                                          grants, adds_sorted=True)
        return lanes2, res.rm_keys, res.rm_vals, n_lane, n_drop

    def _skip(lanes_in):
        st = lanes_in.stats
        lanes2 = lanes_in._replace(
            quiet_ticks=quiet1,
            stats=st._replace(n_ticks=st.n_ticks + 1))
        return (lanes2, jnp.full((L, rl), INF, _F32),
                jnp.full((L, rl), EMPTY_VAL, _I32),
                jnp.zeros((L,), _I32), jnp.zeros((), _I32))

    lanes, res_k, res_v, n_lane, n_drop = jax.lax.cond(
        lane_work, _do, _skip, state.lanes)

    result = _fold_results(n_matched, matched_k, matched_v, res_k,
                           res_v, n_lane)

    new_state = ShardedState(
        lanes=lanes,
        rng=key,
        route=route,
        route_inv=route_inv,
        tick_idx=state.tick_idx + 1,
        n_router_dropped=state.n_router_dropped + n_drop,
        elim_ema=elim_ema,
        balance_ema=balance_ema,
        disp_ema=disp_ema,
        n_preroute_elim=state.n_preroute_elim + n_matched,
        n_preroute_ticks=state.n_preroute_ticks + elim_ran.astype(_I32),
    )
    return new_state, result


def _fold_results(n_matched, matched_k, matched_v, res_k, res_v,
                  n_lane) -> ShardedTickResult:
    """Fold per-lane serves into one compacted stream: [pre-route matched
    | lane serves] (no global sort: callers of a relaxed queue get a
    near-min *set*, not an order).  Every lane serves a PREFIX of its
    result row (the removed stream is [imm elim | merged prefix |
    moveHead prefix], each segment dense), so compaction is
    ragged-segment arithmetic over the lane counts — a [out_w, L]
    compare-all instead of an [out_w, L*rl] searchsorted scan.
    n_matched + lane grants <= rm_count <= out_w (grants are allocated
    from the residual), so the prefix can never push a lane serve off
    the end.  Shared with the distributed queue (core/distributed.py),
    which runs it on the all-device result stack AFTER shard_map — the
    lane segments of the global stream are exactly the exclusive prefix
    over per-device serve counts, so assembly needs no coordinator."""
    L, rl = res_k.shape
    w = matched_k.shape[0]
    out_w = max(w, L * rl)
    cum = jnp.cumsum(n_lane)
    offs = cum - n_lane
    n_served = cum[L - 1]
    j = jnp.arange(out_w, dtype=_I32)
    jl = j - n_matched                     # rank within the lane segment
    row = jnp.clip(kops.searchsorted_last(cum, jnp.maximum(jl, 0),
                                          side="right"), 0, L - 1)
    col = jnp.clip(jl - offs[row], 0, rl - 1)
    got_lane = (jl >= 0) & (jl < n_served)
    in_matched = j < n_matched
    flat = row * rl + col
    rm_keys = jnp.where(
        in_matched, matched_k[jnp.clip(j, 0, w - 1)],
        jnp.where(got_lane, res_k.reshape(-1)[flat], INF))
    rm_vals = jnp.where(
        in_matched, matched_v[jnp.clip(j, 0, w - 1)],
        jnp.where(got_lane, res_v.reshape(-1)[flat], EMPTY_VAL))
    got = in_matched | got_lane
    return ShardedTickResult(rm_keys, rm_vals, got)


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def tick(cfg: ShardedPQConfig, state: ShardedState, add_keys, add_vals,
         add_mask, rm_count) -> Tuple[ShardedState, ShardedTickResult]:
    """One synchronized round over all lanes (route -> fused lane-major
    tick -> fold).

    add_keys/add_vals/add_mask: [W] un-sharded op batch; rm_count: scalar.
    `state` is DONATED — do not touch the argument after the call.
    Returns up to rm_count near-minimal (key, val) pairs, compacted into
    a [max(W, L * lane.r_max)]-wide result (see ShardedTickResult;
    relaxed semantics — see module docstring).
    """
    return _tick_impl(cfg, state, add_keys, add_vals, add_mask, rm_count)


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def tick_n(cfg: ShardedPQConfig, state: ShardedState, add_keys, add_vals,
           add_mask, rm_counts) -> Tuple[ShardedState, ShardedTickResult]:
    """`lax.scan` multi-tick driver over [T, ...]-stacked op batches;
    `state` is DONATED.  One dispatch for T synchronized rounds."""
    def body(s, xs):
        return _tick_impl(cfg, s, *xs)

    return jax.lax.scan(body, state,
                        (add_keys, add_vals, add_mask, rm_counts))


# ---------------------------------------------------------------------------
# introspection helpers (tests, benches)
# ---------------------------------------------------------------------------

class ShardedStats(NamedTuple):
    """Aggregated per-path counters of the whole sharded queue.

    ``lane`` is the per-lane :class:`pqueue.PQStats` REDUCED over the
    lane axis (every counter summed), so the paper's Figs. 7–8
    accounting reads the same way it does for the single queue; the
    queue-level counters cover what no lane can see — the pre-route
    elimination pass and the router."""

    lane: pqueue.PQStats            # per-lane counters summed over L
    n_preroute_elim: jnp.ndarray    # pairs matched BEFORE routing
    n_preroute_ticks: jnp.ndarray   # ticks where the pre-route pass ran
    n_router_dropped: jnp.ndarray
    n_ticks: jnp.ndarray            # sharded ticks (== tick_idx)
    elim_ema: jnp.ndarray           # controller signals, as of now
    balance_ema: jnp.ndarray
    disp_ema: jnp.ndarray           # add-batch key-dispersion EMA
    # serving observability (repro.serving): the admission controller
    # gates on queue depth, and with priority = deadline the union
    # min-of-lane-heads IS the next-to-serve deadline — its distance
    # from the serving clock is the age/slack of the queue frontier.
    depth: jnp.ndarray              # total resident elements (== size())
    min_head: jnp.ndarray           # union min of lane heads (INF if empty)


def stats(state: ShardedState) -> ShardedStats:
    """Aggregate the queue's counters (lane reduction + queue level)."""
    return ShardedStats(
        lane=jax.tree.map(lambda x: x.sum(axis=0), state.lanes.stats),
        n_preroute_elim=state.n_preroute_elim,
        n_preroute_ticks=state.n_preroute_ticks,
        n_router_dropped=state.n_router_dropped,
        n_ticks=state.tick_idx,
        elim_ema=state.elim_ema,
        balance_ema=state.balance_ema,
        disp_ema=state.disp_ema,
        depth=size(state),
        min_head=_union_min(state.lanes),
    )


def size(state: ShardedState) -> jnp.ndarray:
    return (state.lanes.seq_len + state.lanes.par_count).sum()


def lane_sizes(state: ShardedState) -> jnp.ndarray:
    return state.lanes.seq_len + state.lanes.par_count


def relax_bound(cfg: ShardedPQConfig, rm_count: int) -> int:
    """The c of the c-relaxed contract checked by tests/test_sharded.py.

    Every key removed by a tick of r removes lies within the c smallest
    of the union state (pre-tick contents + that tick's adds), with

        c = r + L * ceil(r / L) + 2 * L * lane.a_max.

    The three terms: (1) the r requested; (2) each lane serves its own
    exact minima, so an even-split grant displaces a removed key by at
    most the other lanes' same-prefix holdings (~(L-1) * ceil(r/L) under
    a balanced router); (3) a lane may also *eliminate* an incoming add
    against its local head, which trails the union minimum by at most the
    lane's share of recent arrivals (bounded by its a_max batch quota per
    stick window).  Like the MultiQueues rank guarantees this envelope is
    probabilistic in the router's balance, not adversarial-deterministic;
    the constant 2 gives the measured worst case on the bench workloads
    (~19L displacement at W=64) a ~2x margin.

    L = 1 is exact (c = r): the single lane holds the whole union, its
    head IS the union minimum, and a pre-route-eliminated add is <= that
    head — so every served key is a true prefix minimum (the quality
    harness pins rank error identically 0 there; tests/test_quality.py).
    """
    r = int(rm_count)
    if cfg.n_lanes == 1:
        return r
    return (r + cfg.n_lanes * (-(-r // cfg.n_lanes))
            + 2 * cfg.n_lanes * cfg.lane.a_max)


# ---------------------------------------------------------------------------
# elastic lane count (fold/unfold at runtime)
# ---------------------------------------------------------------------------
#
# The lane count L is static per-config (every shape depends on it), but
# the router's permuted round-robin tolerates L *changing between
# configs*: a route is re-derived from (rng, W, L) alone, grants are
# re-derived from the [L] lane summaries every tick, and no lane ever
# holds another lane's state.  Folding lanes is therefore a host-level
# config swap: keep the surviving lanes' PQState rows bit-for-bit, drain
# the dropped lanes' resident elements into an ordinary add batch, and
# re-derive the control plane (PRNG, permutation, inverse) for the new
# L.  This is the mechanism behind the fault-tolerant mesh resize
# (repro.core.distributed.resize: a dead device's lanes fold over the
# survivors) and behind elastic lane scaling generally.

def resident(cfg: ShardedPQConfig, lanes: pqueue.PQState):
    """Enumerate every resident element of the stacked lanes.

    Returns ``(keys [L, cap], vals [L, cap], live [L, cap])`` with
    cap = seq_cap + par_cap: the sequential part is its dense sorted
    prefix (``seq_len``), the parallel part is every finite bucket slot
    (INF = empty by the bucket invariant).  Pure shape-static jnp math —
    usable under jit, though the elastic path calls it host-side."""
    lc = cfg.lane
    live_seq = (jnp.arange(lc.seq_cap, dtype=_I32)[None, :]
                < lanes.seq_len[:, None])
    bk = lanes.buckets.reshape(lanes.buckets.shape[0], -1)
    bv = lanes.bvals.reshape(lanes.bvals.shape[0], -1)
    live_par = jnp.isfinite(bk)
    keys = jnp.concatenate([lanes.seq_keys, bk], axis=-1)
    vals = jnp.concatenate([lanes.seq_vals, bv], axis=-1)
    live = jnp.concatenate([live_seq, live_par], axis=-1)
    return keys, vals, live


def fold_lanes(cfg: ShardedPQConfig, state: ShardedState, keep):
    """Shrink the queue to the ``keep`` lanes (host-level, eager).

    ``keep`` is the ordered list of surviving lane indices.  Surviving
    lanes' PQState rows are carried bit-for-bit; the dropped lanes'
    resident elements are DRAINED into a flat (keys, vals) batch the
    caller re-adds through ordinary ticks (the router's permuted
    round-robin re-maps them over the survivors — that re-add is the
    "remap" half of drain-and-remap).  The replicated control plane is
    re-derived for the new L: the PRNG advances by one fold_in (split)
    step, and a fresh permutation + inverse are built from it, exactly
    as a resample tick would.  Counters (tick_idx, stats, controller
    EMAs) carry over — the fold changes placement, not history.

    Returns ``(new_cfg, new_state, drained_keys, drained_vals)`` (the
    drained arrays are 1-D np arrays, possibly empty).  Multiset
    conservation — kept + drained == pre-fold resident — is asserted
    here; the relax-bound contract after the fold is
    ``relax_bound(new_cfg, r)`` from the first post-fold tick (pinned by
    tests/test_dist_resize.py).
    """
    keep = [int(i) for i in keep]
    L = cfg.n_lanes
    if sorted(set(keep)) != sorted(keep) or not keep:
        raise ValueError("keep must be a nonempty list of distinct lanes")
    if any(i < 0 or i >= L for i in keep):
        raise ValueError(f"keep out of range for L={L}")
    drop = [i for i in range(L) if i not in keep]
    new_cfg = dataclasses.replace(cfg, n_lanes=len(keep))

    keys, vals, live = resident(cfg, state.lanes)
    keys = np.asarray(keys)
    vals = np.asarray(vals)
    live = np.asarray(live)
    if drop:
        dmask = live[drop]
        drained_keys = keys[drop][dmask].astype(np.float32)
        drained_vals = vals[drop][dmask].astype(np.int32)
    else:
        drained_keys = np.zeros((0,), np.float32)
        drained_vals = np.zeros((0,), np.int32)
    sizes = np.asarray(state.lanes.seq_len + state.lanes.par_count)
    want = int(sizes[drop].sum()) if drop else 0
    assert len(drained_keys) == want, (
        f"drain miscount: enumerated {len(drained_keys)}, lanes report "
        f"{want} — bucket invariant violated")

    idx = jnp.asarray(keep, _I32)
    lanes_new = jax.tree.map(lambda x: jnp.asarray(x)[idx], state.lanes)
    # re-derive the replicated control plane on the new lane count: one
    # PRNG step (as a resample tick would take), then a fresh permuted
    # round-robin over the SAME op-batch width with the new L
    key2, sub = jax.random.split(jnp.asarray(state.rng))
    route = _fresh_route(sub, cfg.a_total, len(keep))
    route_inv = jnp.argsort(route, stable=True).astype(_I32)
    new_state = ShardedState(
        lanes=lanes_new,
        rng=key2,
        route=route,
        route_inv=route_inv,
        tick_idx=jnp.asarray(state.tick_idx),
        n_router_dropped=jnp.asarray(state.n_router_dropped),
        elim_ema=jnp.asarray(state.elim_ema),
        balance_ema=jnp.asarray(state.balance_ema),
        disp_ema=jnp.asarray(state.disp_ema),
        n_preroute_elim=jnp.asarray(state.n_preroute_elim),
        n_preroute_ticks=jnp.asarray(state.n_preroute_ticks),
    )
    return new_cfg, new_state, drained_keys, drained_vals


def unfold_lanes(cfg: ShardedPQConfig, state: ShardedState, n_lanes: int):
    """Grow the queue to ``n_lanes`` by appending EMPTY lanes (the
    scale-out inverse of :func:`fold_lanes`: a recovered or new device's
    lanes join with nothing in them and fill through the re-derived
    router).  Returns ``(new_cfg, new_state)``; existing lanes carry
    bit-for-bit, so the resident multiset is untouched."""
    L = cfg.n_lanes
    if n_lanes < L:
        raise ValueError("unfold_lanes cannot shrink; use fold_lanes")
    new_cfg = dataclasses.replace(cfg, n_lanes=n_lanes)
    if n_lanes == L:
        return new_cfg, state
    fresh = _stack_init(dataclasses.replace(cfg, n_lanes=n_lanes - L))
    lanes_new = jax.tree.map(
        lambda a, b: jnp.concatenate([jnp.asarray(a), b], axis=0),
        state.lanes, fresh)
    key2, sub = jax.random.split(jnp.asarray(state.rng))
    route = _fresh_route(sub, cfg.a_total, n_lanes)
    new_state = state._replace(
        lanes=lanes_new, rng=key2, route=route,
        route_inv=jnp.argsort(route, stable=True).astype(_I32))
    return new_cfg, new_state