"""Batched adaptive priority queue with elimination and combining (APEX-Q core).

This is the TPU-native re-realization of Calciu, Mendes & Herlihy 2014
("The Adaptive Priority Queue with Elimination and Combining").  See
DESIGN.md §2–3 for the full mapping; in brief:

* the asynchronous *elimination array* becomes a vectorized batch
  elimination pass over a tick's operation batch;
* the *server thread* (flat combining) becomes the fused combine stage of
  :func:`tick` — one agent applies all surviving ops at amortized cost;
* the *sequential skiplist part* becomes a sorted array head
  (``seq_keys``/``seq_vals``), consumed by pointer bumps;
* the *parallel skiplist part* becomes a key-range bucketed store where
  large-key adds scatter-append without conflicts (disjoint-access
  parallelism);
* ``moveHead``/``chopHead`` and the paper's adaptive detach policy
  (halve over N=1000, double under M=100, bounds [8, 65536]) transfer
  verbatim.

The hot paths are *sortless* (DESIGN.md §6): bucket ranges are disjoint
and ordered, so moveHead is a selection (``ops.extract_k_bucketed``) and
every merge of already-sorted streams is a rank merge — the only
comparison sorts left are the a_max-wide add-batch sort and BCAP-wide
per-bucket row sorts.  The combine pass's merge (``ops.merge_sorted``)
runs every tick over the seq_cap + a_max stream, so on the jnp backend
it has no data-dependent gather: XLA:TPU runs a gather one index at a
time, while counted co-ranks, a static-shift placement network and
dynamic-slice windows are elementwise work at memory speed.  The rare
repairs merge with :func:`rank_merge_kv` (searchsorted + gather: XLA CPU
serializes scatters); the Pallas kernel is a one-hot matmul.

Correctness contract (checked against a heapq oracle in
``tests/test_pq_properties.py``): a tick with adds ``X`` and ``r`` removes
returns exactly the ``r`` smallest keys of ``PQ ∪ X`` (as a multiset), and
the post-state contains the rest.  This is the batch-sequential equivalent
of the paper's linearizability argument (DESIGN.md §2).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import obs
from repro.core.adaptive import update_detach
from repro.core.config import EMPTY_VAL, PQConfig
from repro.kernels import ops as kops

INF = jnp.inf
_I32 = jnp.int32
_F32 = jnp.float32


class PQStats(NamedTuple):
    """Cumulative per-path counters (reproduces the paper's Figs. 7–8 and
    Table 1 accounting)."""

    add_imm_elim: jnp.ndarray   # adds eliminated immediately (v <= minValue)
    add_upc_elim: jnp.ndarray   # adds eliminated after "aging" in the batch
    add_seq: jnp.ndarray        # adds combined into the sequential part
    add_par: jnp.ndarray        # adds inserted in parallel (SL::addPar)
    rm_seq: jnp.ndarray         # removes served from the sequential part
    rm_par: jnp.ndarray         # removes served via emergency moveHead
    rm_empty: jnp.ndarray       # removes that found an empty queue
    n_movehead: jnp.ndarray     # SL::moveHead() events
    n_chophead: jnp.ndarray     # SL::chopHead() events
    n_rebalance: jnp.ndarray    # parallel-part rebalances (bucket overflow)
    n_spill: jnp.ndarray        # sequential->parallel spills (partial chop)
    n_dropped: jnp.ndarray      # items dropped at total-capacity (should be 0)
    n_ticks: jnp.ndarray
    n_removes: jnp.ndarray      # total removeMin requests (for Table 1 ratios)

    @staticmethod
    def zeros() -> "PQStats":
        # distinct buffers per field: tick donates the state, and XLA
        # rejects donating one buffer twice
        return PQStats(*(jnp.zeros((), _I32) for _ in PQStats._fields))


class PQState(NamedTuple):
    """Functional state of the dual-structure priority queue (a pytree)."""

    # sequential part: sorted ascending, INF-padded beyond seq_len
    seq_keys: jnp.ndarray       # [seq_cap] f32
    seq_vals: jnp.ndarray       # [seq_cap] i32
    seq_len: jnp.ndarray        # scalar i32

    # parallel part: key-range buckets (2-level radix "skiplist")
    buckets: jnp.ndarray        # [NB, BCAP] f32 (INF = empty slot)
    bvals: jnp.ndarray          # [NB, BCAP] i32
    bcounts: jnp.ndarray        # [NB] i32
    splitters: jnp.ndarray      # [NB] f32, splitters[0] = -INF, nondecreasing
    par_min: jnp.ndarray        # scalar f32 (INF if parallel part empty)
    par_count: jnp.ndarray      # scalar i32

    # paper state
    min_value: jnp.ndarray      # scalar f32 (paper's minValue; INF if empty)
    last_seq: jnp.ndarray       # scalar f32 (paper's lastSeq.key; -INF if none)
    detach_n: jnp.ndarray       # scalar i32 (adaptive moveHead size)
    ins_since_move: jnp.ndarray  # scalar i32 (insertions since last moveHead)
    quiet_ticks: jnp.ndarray    # scalar i32 (ticks without removes)

    stats: PQStats


class TickResult(NamedTuple):
    rm_keys: jnp.ndarray        # [r_max] f32; INF where unserved/masked
    rm_vals: jnp.ndarray        # [r_max] i32; EMPTY_VAL where unserved
    rm_served: jnp.ndarray      # [r_max] bool
    # which separable passes this tick needed: [5] i32 (combine, scatter,
    # rebalance, moveHead, chopHead) — the predicates the sharded driver
    # reduces across lanes (defaults to an empty pytree node for legacy
    # 3-arg construction)
    repairs: tuple = ()


def init(cfg: PQConfig) -> PQState:
    nb, bc, sc = cfg.n_buckets, cfg.bucket_cap, cfg.seq_cap
    splitters = jnp.full((nb,), INF, _F32).at[0].set(-INF)
    return PQState(
        seq_keys=jnp.full((sc,), INF, _F32),
        seq_vals=jnp.full((sc,), EMPTY_VAL, _I32),
        seq_len=jnp.zeros((), _I32),
        buckets=jnp.full((nb, bc), INF, _F32),
        bvals=jnp.full((nb, bc), EMPTY_VAL, _I32),
        bcounts=jnp.zeros((nb,), _I32),
        splitters=splitters,
        par_min=jnp.asarray(INF, _F32),
        par_count=jnp.zeros((), _I32),
        min_value=jnp.asarray(INF, _F32),
        last_seq=jnp.asarray(-INF, _F32),
        detach_n=jnp.asarray(cfg.detach_init, _I32),
        ins_since_move=jnp.zeros((), _I32),
        quiet_ticks=jnp.zeros((), _I32),
        stats=PQStats.zeros(),
    )


# ---------------------------------------------------------------------------
# small vectorized helpers
# ---------------------------------------------------------------------------

def _sort_kv(keys, vals):
    order = jnp.argsort(keys)
    return keys[order], vals[order]


def _shift_left(arr, n, fill):
    """arr shifted left by (traced) n along the last axis, filled with
    `fill` on the right.  `n` may carry leading dims matching arr's."""
    size = arr.shape[-1]
    idx = jnp.expand_dims(jnp.asarray(n, _I32), -1) + jnp.arange(
        size, dtype=_I32)
    out = jnp.take_along_axis(arr, jnp.clip(idx, 0, size - 1), axis=-1)
    return jnp.where(idx < size, out, fill)


def _take_window(arr, start, out_len, fill):
    """arr[..., start : start+out_len] with static out_len, `fill` past
    the end; `start` >= 0.  `start` may carry leading dims matching
    arr's: those rows take a gather.  A scalar start is one dynamic slice
    of the row padded by out_len fill values: the same window, with no
    gather (XLA:TPU runs a general gather per element)."""
    size = arr.shape[-1]
    start = jnp.asarray(start, _I32)
    if start.ndim == 0:
        pad = jnp.full(arr.shape[:-1] + (out_len,), fill, arr.dtype)
        return jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([arr, pad], axis=-1), start, out_len, axis=-1)
    idx = jnp.expand_dims(start, -1) + jnp.arange(
        out_len, dtype=_I32)
    out = jnp.take_along_axis(arr, jnp.clip(idx, 0, size - 1), axis=-1)
    return jnp.where(idx < size, out, fill)


def _where_lead(pred, a, b):
    """jnp.where with `pred` broadcast against extra trailing axes of a/b
    (per-lane selection in the lane-major repair passes)."""
    extra = a.ndim - jnp.asarray(pred).ndim
    return jnp.where(jnp.reshape(pred, jnp.shape(pred) + (1,) * extra),
                     a, b)


def _select_tree(pred, t_true, t_false):
    """Per-lane pytree select (leaves may have mixed ranks)."""
    return jax.tree.map(lambda x, y: _where_lead(pred, x, y),
                        t_true, t_false)


def rank_merge_kv(ak, av, bk, bv):
    """Rank-merge two sorted (key, val) streams (INF-padded).

    Co-rank gather: a[i] lands at output rank i + #{b < a[i]} (ties
    a-first), so for each output position j the source is recovered with
    one searchsorted against those ranks — all gathers, no scatter (XLA
    CPU serializes scatters; gathers vectorize), and no O((n+m) log(n+m))
    full sort.  The repairs' merge (n = par_cap, where compare-all
    counting would be n * m pairs); the flags lane is dead here and
    DCE'd under jit.
    """
    ok, ov, _ = kops._merge_sorted_corank(
        ak, av, jnp.zeros_like(av), bk, bv, jnp.zeros_like(bv))
    return ok, ov


# ---------------------------------------------------------------------------
# parallel part primitives (the bucketed "skiplist" suffix)
# ---------------------------------------------------------------------------

class ParPart(NamedTuple):
    buckets: jnp.ndarray
    bvals: jnp.ndarray
    bcounts: jnp.ndarray
    splitters: jnp.ndarray
    par_min: jnp.ndarray
    par_count: jnp.ndarray


def _par_of(state: PQState) -> ParPart:
    return ParPart(state.buckets, state.bvals, state.bcounts,
                   state.splitters, state.par_min, state.par_count)


def flatten_parallel(cfg: PQConfig, par: ParPart):
    """All parallel items as a sorted flat (keys, vals) pair of size par_cap.

    Sortless: bucket key ranges are disjoint and ordered (the splitter
    directory routes every insert), so the global order is just the
    per-bucket sorted runs concatenated by bucket rank — one shared
    gather-only implementation in ops.sorted_runs_gather (O(L log BCAP)
    row sorts instead of the old O(L log L) global sort).  DESIGN.md §6.
    (The -1 padding of the shared helper IS this module's EMPTY_VAL.)
    """
    fk, fv, _, _ = kops.sorted_runs_gather(par.buckets, par.bvals,
                                           par.bcounts, cfg.par_cap)
    return fk, fv


def _redistribute(cfg: PQConfig, flat_k, flat_v, total):
    """Evenly refill the buckets from a sorted flat stream.

    The skiplist analogue of rebalancing: bucket i receives the sorted rank
    range [i*per, (i+1)*per), and splitters are the per-bucket minima, so
    bucket key ranges stay disjoint and ordered.  Accepts leading lane
    dims on every argument (the sharded repair passes redistribute all
    lanes in one lane-major call); everything is pure window gathers —
    XLA CPU serializes scatters.
    """
    nb, bc = cfg.n_buckets, cfg.bucket_cap
    size = flat_k.shape[-1]
    lead = flat_k.shape[:-1]
    total = jnp.asarray(total, _I32)
    per = jnp.clip((total + nb - 1) // jnp.asarray(nb, _I32), 1, bc)
    capacity = nb * per
    kept = jnp.minimum(total, capacity)
    dropped = total - kept

    # bucket i takes the stream window [i*per, (i+1)*per) — a pure gather
    rows = jnp.arange(nb, dtype=_I32)[:, None]
    slot = jnp.arange(bc, dtype=_I32)[None, :]
    per_b = per[..., None, None]
    idx = rows * per_b + slot                       # [..., nb, bc]
    take = (slot < per_b) & (idx < kept[..., None, None])
    src = jnp.clip(idx, 0, size - 1).reshape(lead + (nb * bc,))
    gk = jnp.take_along_axis(flat_k, src, axis=-1).reshape(
        lead + (nb, bc))
    gv = jnp.take_along_axis(flat_v, src, axis=-1).reshape(
        lead + (nb, bc))
    buckets = jnp.where(take, gk, INF)
    bvals = jnp.where(take, gv, EMPTY_VAL)
    bcounts = jnp.clip(kept[..., None]
                       - jnp.arange(nb, dtype=_I32) * per[..., None],
                       0, per[..., None]).astype(_I32)

    sp_idx = jnp.arange(nb, dtype=_I32) * per[..., None]     # [..., nb]
    sp = jnp.take_along_axis(flat_k, jnp.clip(sp_idx, 0, size - 1),
                             axis=-1)
    sp = jnp.where(sp_idx < kept[..., None], sp, INF)
    splitters = sp.at[..., 0].set(-INF)

    par_min = jnp.where(kept > 0, flat_k[..., 0], jnp.asarray(INF, _F32))
    return ParPart(buckets, bvals, bcounts, splitters, par_min,
                   kept.astype(_I32)), dropped.astype(_I32)


def scatter_parallel(cfg: PQConfig, par: ParPart, keys, vals, *,
                     assume_sorted: bool = False):
    """SL::addPar(): disjoint-access parallel insert of a key batch.

    Fast path: route each key through the splitter directory
    (the skiplist's top level) and segment-append within its bucket.
    On (rare) bucket overflow, fall back to a full rebalance — the batch
    analogue of skiplist restructuring — built from a rank-merge of the
    per-bucket sorted runs with the (sorted) incoming batch; no global
    sort on either path.

    Invalid entries are INF keys; they are dropped.  `assume_sorted=True`
    (the tick's path: its batch is a rank-merge of two sorted streams)
    skips the grouping sort entirely: sorted keys route to nondecreasing
    bucket ids, so segment ranks fall out of a searchsorted against the
    batch itself.
    Returns (new_par, n_rebalance, n_dropped).
    """
    nb, bc = cfg.n_buckets, cfg.bucket_cap
    size = keys.shape[0]
    valid = keys < INF

    bidx = jnp.clip(
        jnp.searchsorted(par.splitters, keys, side="right") - 1, 0, nb - 1
    ).astype(_I32)
    bidx = jnp.where(valid, bidx, nb)        # invalid -> past the last bucket

    if assume_sorted:
        # keys ascending (INF suffix) => bidx already nondecreasing
        sb, sk, sv = bidx, keys, vals
    else:
        # stable sort by bucket id so each bucket's arrivals are one
        # contiguous segment of the batch
        order = jnp.argsort(bidx, stable=True)
        sb = bidx[order]
        sk = keys[order]
        sv = vals[order]
    # per-bucket arrival segments of the (sorted-by-bucket) batch; the
    # append is then a gather of each segment behind the row's live
    # prefix — no scatter (XLA CPU serializes scatters)
    rows = jnp.arange(nb, dtype=_I32)
    seg_start = jnp.searchsorted(sb, rows, side="left").astype(_I32)
    seg_len = (jnp.searchsorted(sb, rows, side="right").astype(_I32)
               - seg_start)
    new_counts = par.bcounts + seg_len

    overflow = jnp.any(new_counts > bc)

    def fast(par):
        slot = jnp.arange(bc, dtype=_I32)[None, :]
        old = slot < par.bcounts[:, None]
        appended = ~old & (slot < new_counts[:, None])
        src = jnp.clip(seg_start[:, None] + (slot - par.bcounts[:, None]),
                       0, size - 1)
        buckets = jnp.where(appended, sk[src],
                            jnp.where(old, par.buckets, INF))
        bvals = jnp.where(appended, sv[src],
                          jnp.where(old, par.bvals, EMPTY_VAL))
        kmin = jnp.min(jnp.where(valid, keys, INF))
        par_min = jnp.minimum(par.par_min, kmin)
        par_count = par.par_count + valid.sum(dtype=_I32)
        return (ParPart(buckets, bvals, new_counts, par.splitters, par_min,
                        par_count),
                jnp.zeros((), _I32), jnp.zeros((), _I32))

    def slow(par):
        fk, fv = flatten_parallel(cfg, par)
        ck = jnp.where(valid, keys, INF)
        cv = jnp.where(valid, vals, EMPTY_VAL)
        if not assume_sorted:
            ck, cv = _sort_kv(ck, cv)      # batch-sized sort only
        allk, allv = rank_merge_kv(fk, fv, ck, cv)
        total = par.par_count + valid.sum(dtype=_I32)
        newpar, dropped = _redistribute(cfg, allk, allv, total)
        return newpar, jnp.ones((), _I32), dropped

    return jax.lax.cond(overflow, slow, fast, par)


# ---------------------------------------------------------------------------
# the tick: elimination -> combining -> parallel adds -> moveHead/chopHead
#
# Split (DESIGN.md §6.1) into an unconditional *head* (`_tick_head`:
# batch sort, immediate elimination, small/large split) and five
# separable data-dependent passes — combine (`_pass_combine`), scatter
# (`_pass_scatter`), and the three repairs (`_repair_rebal_move`,
# `_repair_rebalance`, `_repair_move`, `_repair_chop`) — whose
# predicates ride the mid-tick carry.  The single-queue `tick` runs the
# combine/scatter passes inline (they are its whole job) and each
# repair under its own `lax.cond`; the sharded queue reduces every
# predicate across lanes OUTSIDE its vmap and runs each pass lane-major
# under one batch-level cond — so `vmap`'s cond→select lowering can no
# longer force every lane to pay every rare path on every tick, and a
# drain tick whose batch fully eliminates pays neither the combine
# merge nor the scatter.  All passes are leading-dim polymorphic: the
# same code serves the scalar single-queue branches and the [L, ...]
# lane-major sharded branches (bit-identical results either way — they
# are pure gathers/compares).
# ---------------------------------------------------------------------------

class RepairPending(NamedTuple):
    """Pass predicates + operands exposed by :func:`_tick_head`.

    Every data-dependent stage of a tick — the combine merge, the
    parallel scatter, and the three repairs — is decided here and
    executed by a separable pass, so the sharded driver can reduce each
    predicate across lanes and skip the pass entirely when no lane needs
    it (DESIGN.md §6.1)."""

    need_combine: jnp.ndarray  # bool — seq nonempty or small adds exist
    small_k: jnp.ndarray       # [a_max] f32 sorted small adds (INF-padded)
    small_v: jnp.ndarray       # [a_max] i32
    large_k: jnp.ndarray       # [a_max] f32 sorted large adds (INF-padded)
    large_v: jnp.ndarray       # [a_max] i32
    need_scatter: jnp.ndarray  # bool — pend batch nonempty: SL::addPar()
    pend_k: jnp.ndarray        # [a_max] f32 sorted par-bound batch
    pend_v: jnp.ndarray        # [a_max] i32
    need_rebal: jnp.ndarray    # bool — bucket overflow (set by scatter)
    need_move: jnp.ndarray     # bool — remove shortfall: SL::moveHead()
    r2: jnp.ndarray            # i32 removes left for the parallel part
    move_off: jnp.ndarray      # i32 offset of moveHead keys in rm_keys
    detach_arg: jnp.ndarray    # i32 pre-update detach_n (sizes the extract)
    need_chop: jnp.ndarray     # bool — quiet stream: SL::chopHead()


class TickMid(NamedTuple):
    """Mid-tick carry between the head, the passes, and finish."""

    nsk: jnp.ndarray          # [seq_cap] f32 tentative sequential part
    nsv: jnp.ndarray          # [seq_cap] i32
    new_len: jnp.ndarray      # i32
    par: ParPart
    rm_keys: jnp.ndarray      # [r_max] f32 (merge/moveHead segments INF
    rm_vals: jnp.ndarray      # [r_max] i32  until their passes run)
    rm_count: jnp.ndarray     # i32
    pending: RepairPending
    # raw counters, assembled into PQStats once in _tick_finish
    n_imm: jnp.ndarray
    n_upc: jnp.ndarray
    n_rm_seq: jnp.ndarray
    n_addseq: jnp.ndarray
    n_par_adds: jnp.ndarray
    spilled: jnp.ndarray      # i32 0/1
    n_rm_par: jnp.ndarray     # filled by the moveHead repairs
    n_drop_rep: jnp.ndarray   # filled by rebalance/chop repairs
    detach_n: jnp.ndarray     # finalized by _tick_preds
    ins_since_move: jnp.ndarray
    quiet: jnp.ndarray
    stats0: PQStats           # pre-tick stats (base for finish)


def _scatter_fast(cfg: PQConfig, par: ParPart, keys, vals):
    """SL::addPar() fast path: segment-append a sorted batch along the
    splitter routes.  Leading-dim polymorphic.  Returns (appended_par,
    overflow); when `overflow` the append is WRONG (slots past
    bucket_cap were silently clipped) — the caller must discard it and
    queue the batch for the rebalance repair pass instead."""
    nb, bc = cfg.n_buckets, cfg.bucket_cap
    size = keys.shape[-1]
    lead = keys.shape[:-1]
    valid = keys < INF
    # keys ascending (INF suffix) and splitters nondecreasing: bucket b's
    # arrival segment is [#keys < splitters[b], #keys < splitters[b+1])
    # (a key equal to splitters[b] routes to b; the INF suffix routes
    # nowhere) — ONE searchsorted of the nb+1 boundaries against the
    # batch replaces per-key bucket ids plus two segment searches
    bounds = jnp.concatenate(
        [par.splitters[..., 1:],
         jnp.broadcast_to(jnp.asarray(INF, _F32), lead + (1,))], axis=-1)
    ends = kops.searchsorted_last(keys, bounds, side="left")  # [..., nb]
    seg_start = jnp.concatenate(
        [jnp.zeros(lead + (1,), _I32), ends[..., :-1]], axis=-1)
    seg_len = ends - seg_start
    new_counts = par.bcounts + seg_len
    overflow = jnp.any(new_counts > bc, axis=-1)

    slot = jnp.arange(bc, dtype=_I32)
    old = slot < par.bcounts[..., None]
    appended = ~old & (slot < new_counts[..., None])
    src = jnp.clip(seg_start[..., None] + (slot - par.bcounts[..., None]),
                   0, size - 1).reshape(lead + (nb * bc,))
    gk = jnp.take_along_axis(keys, src, axis=-1).reshape(lead + (nb, bc))
    gv = jnp.take_along_axis(vals, src, axis=-1).reshape(lead + (nb, bc))
    buckets = jnp.where(appended, gk, jnp.where(old, par.buckets, INF))
    bvals = jnp.where(appended, gv,
                      jnp.where(old, par.bvals, EMPTY_VAL))
    kmin = jnp.min(jnp.where(valid, keys, INF), axis=-1)
    par_min = jnp.minimum(par.par_min, kmin)
    par_count = par.par_count + valid.sum(axis=-1, dtype=_I32)
    return ParPart(buckets, bvals, jnp.minimum(new_counts, bc),
                   par.splitters, par_min, par_count), overflow


@jax.named_scope(obs.PQ_HEAD)
def _tick_head(cfg: PQConfig, state: PQState, add_keys, add_vals,
               add_mask, rm_count, *,
               adds_sorted: bool = False) -> TickMid:
    """Steps 0–2: batch sort, immediate elimination, small/large split.

    The unconditional prefix of a tick — everything data-dependent
    (combine, scatter, repairs) is a separable pass gated by the
    predicates this head (and the passes themselves) expose, so a
    sharded driver can skip whole passes when no lane needs them.  The
    head leaves `mid` in the exact post-tick shape for a lane whose
    every pass is skipped: empty head (such a lane had an empty
    sequential part — `need_combine` covers the rest), untouched par,
    removal stream = the eliminated prefix only.

    ``adds_sorted=True`` (static) promises add_keys is already stably
    key-sorted with an INF suffix and add_mask a matching prefix — the
    sharded router's fused lane-grouping sort delivers exactly that, so
    each lane skips its own a_max-wide sort.
    """
    A, R, SC = cfg.a_max, cfg.r_max, cfg.seq_cap
    rm_count = jnp.minimum(jnp.asarray(rm_count, _I32), R)

    # -- 0. sanitize + sort the add batch (the elimination array) --
    ak = jnp.where(add_mask, add_keys.astype(_F32), INF)
    av = jnp.where(add_mask, add_vals.astype(_I32), EMPTY_VAL)
    if not adds_sorted:
        ak, av, _ = kops.sort_kvf(ak, av, jnp.zeros((A,), _I32),
                                  backend=cfg.backend)
    n_adds = add_mask.sum(dtype=_I32)
    a_valid = jnp.arange(A, dtype=_I32) < n_adds

    # -- 1. immediate elimination: add(v <= minValue) pairs a remove --
    m0 = state.min_value
    n_elig = jnp.sum((ak <= m0) & a_valid, dtype=_I32)
    n_imm = jnp.minimum(n_elig, rm_count)
    rem_k = _shift_left(ak, n_imm, INF)
    rem_v = _shift_left(av, n_imm, EMPTY_VAL)

    # -- 2. split small (<= lastSeq: SL::addPar would refuse) / large --
    small_mask = rem_k <= state.last_seq    # INF never <= finite last_seq
    n_small = small_mask.sum(dtype=_I32)
    small_k = jnp.where(small_mask, rem_k, INF)
    small_v = jnp.where(small_mask, rem_v, EMPTY_VAL)
    large_k = _shift_left(rem_k, n_small, INF)
    large_v = _shift_left(rem_v, n_small, EMPTY_VAL)
    n_par_adds = jnp.sum(large_k < INF, dtype=_I32)

    # -- removal stream segment 1 (the eliminated prefix) --
    ridx = jnp.arange(R, dtype=_I32)
    requested = ridx < rm_count
    in1 = requested & (ridx < n_imm)
    rm_keys = jnp.where(in1, ak[jnp.clip(ridx, 0, A - 1)], INF)
    rm_vals = jnp.where(in1, av[jnp.clip(ridx, 0, A - 1)], EMPTY_VAL)

    z = jnp.zeros((), _I32)
    pending = RepairPending(
        need_combine=(state.seq_len > 0) | (n_small > 0),
        small_k=small_k, small_v=small_v,
        large_k=large_k, large_v=large_v,
        need_scatter=n_par_adds > 0,
        pend_k=large_k, pend_v=large_v,     # combine may fold a spill in
        need_rebal=jnp.zeros((), bool),
        need_move=jnp.zeros((), bool), r2=z, move_off=n_imm,
        detach_arg=state.detach_n,
        need_chop=jnp.zeros((), bool))
    return TickMid(
        # the pre-tick sequential part rides as-is: when the combine
        # pass is skippable (need_combine False) seq_len is 0 and these
        # ARE the empty-head defaults
        nsk=state.seq_keys,
        nsv=state.seq_vals,
        new_len=state.seq_len, par=_par_of(state),
        rm_keys=rm_keys, rm_vals=rm_vals, rm_count=rm_count,
        pending=pending,
        n_imm=n_imm, n_upc=z, n_rm_seq=z, n_addseq=z,
        n_par_adds=n_par_adds, spilled=z, n_rm_par=z, n_drop_rep=z,
        detach_n=state.detach_n, ins_since_move=state.ins_since_move,
        quiet=state.quiet_ticks, stats0=state.stats)


@jax.named_scope(obs.PQ_COMBINE)
def _pass_combine(cfg: PQConfig, mid: TickMid) -> TickMid:
    """Steps 3–4 as a separable pass: rank-merge the sequential part
    with the small adds, consume the remove prefix, spill past the
    threshold, and fold the spill into the par-bound batch.  Lanes with
    `need_combine` False (empty sequential part AND no small adds) keep
    the head's empty-head state bit-for-bit — on a drain-heavy tick
    where elimination absorbs the whole batch, no lane pays the
    seq_cap + a_max merge at all."""
    A, R, SC = cfg.a_max, cfg.r_max, cfg.seq_cap
    M = SC + A
    p = mid.pending
    lead = mid.rm_keys.shape[:-1]
    sel = p.need_combine

    # both streams are already sorted: rank-merge (a gather-free
    # shift network on the jnp backend, one-hot MXU matmul on pallas;
    # the windows below are dynamic slices) — never a full
    # O(M log M) sort of seq_cap + a_max keys.  b-side flags mark the
    # small adds: one consumed inside the remove prefix eliminated
    # *after* the minimum rose past it — the batch form of the paper's
    # "upcoming elimination" (aging in the elimination array).
    small_flag = (p.small_k < INF).astype(_I32)
    mk, mv, mf = kops.merge_sorted(
        mid.nsk, mid.nsv, jnp.zeros(mid.nsk.shape, _I32),
        p.small_k, p.small_v, small_flag, backend=cfg.backend)

    n_small = small_flag.sum(axis=-1, dtype=_I32)
    r1 = mid.rm_count - mid.n_imm
    avail = mid.new_len + n_small       # new_len still == state.seq_len
    s = jnp.minimum(r1, avail)
    consumed = jnp.broadcast_to(jnp.arange(M, dtype=_I32),
                                lead + (M,)) < jnp.expand_dims(s, -1)
    n_upc = jnp.sum(consumed & mf.astype(bool), axis=-1, dtype=_I32)
    n_rm_seq = s - n_upc
    n_addseq = n_small - n_upc

    new_len = avail - s
    nsk = _take_window(mk, s, SC, INF)
    nsv = _take_window(mv, s, SC, EMPTY_VAL)
    in_new = jnp.broadcast_to(jnp.arange(SC, dtype=_I32),
                              lead + (SC,)) < jnp.expand_dims(new_len, -1)
    nsk = jnp.where(in_new, nsk, INF)
    nsv = jnp.where(in_new, nsv, EMPTY_VAL)

    # spill (partial chopHead) if the sequential part grew too large
    spill_cnt = jnp.maximum(0, new_len - cfg.spill_threshold)
    sp_start = new_len - spill_cnt
    sp_k = _take_window(nsk, sp_start, A, INF)
    sp_v = _take_window(nsv, sp_start, A, EMPTY_VAL)
    in_sp = jnp.broadcast_to(jnp.arange(A, dtype=_I32),
                             lead + (A,)) < jnp.expand_dims(spill_cnt, -1)
    sp_k = jnp.where(in_sp, sp_k, INF)
    sp_v = jnp.where(in_sp, sp_v, EMPTY_VAL)
    keep = jnp.broadcast_to(jnp.arange(SC, dtype=_I32),
                            lead + (SC,)) < jnp.expand_dims(sp_start, -1)
    nsk = jnp.where(keep, nsk, INF)
    nsv = jnp.where(keep, nsv, EMPTY_VAL)
    new_len = new_len - spill_cnt

    # par-bound batch: every spill key <= the pre-tick lastSeq (it came
    # from seq ∪ small adds) and every large key > lastSeq, so the
    # sorted union is literally [spill | large].  Width a_max suffices:
    # spill_cnt <= n_small (the post-tick head obeys seq_len <=
    # spill_threshold, so overflow is at most the small adds that caused
    # it) and n_large <= a_max - n_small.
    idx2 = jnp.broadcast_to(jnp.arange(A, dtype=_I32), lead + (A,))
    j_lg = idx2 - jnp.expand_dims(spill_cnt, -1)
    take_sp = idx2 < jnp.expand_dims(spill_cnt, -1)
    in_lg = ~take_sp & (j_lg < A)
    pk = jnp.where(
        take_sp, jnp.take_along_axis(sp_k, jnp.clip(idx2, 0, A - 1), -1),
        jnp.where(in_lg, jnp.take_along_axis(
            p.large_k, jnp.clip(j_lg, 0, A - 1), -1), INF))
    pv = jnp.where(
        take_sp, jnp.take_along_axis(sp_v, jnp.clip(idx2, 0, A - 1), -1),
        jnp.where(in_lg, jnp.take_along_axis(
            p.large_v, jnp.clip(j_lg, 0, A - 1), -1), EMPTY_VAL))

    # removal stream segment 2: the consumed merge prefix, after the
    # n_imm <= R removes elimination served — the R-wide window at
    # R - n_imm of [R fill | mk's first R]
    ridx = jnp.broadcast_to(jnp.arange(R, dtype=_I32), lead + (R,))
    rel = ridx - jnp.expand_dims(mid.n_imm, -1)
    in2 = ((rel >= 0) & (rel < jnp.expand_dims(s, -1))
           & jnp.expand_dims(sel, -1))
    seg = lambda x, fill: _take_window(jnp.concatenate(     # noqa: E731
        [jnp.full(lead + (R,), fill, x.dtype), x[..., :R]], axis=-1),
        R - mid.n_imm, R, fill)
    rm_keys = jnp.where(in2, seg(mk, INF), mid.rm_keys)
    rm_vals = jnp.where(in2, seg(mv, EMPTY_VAL), mid.rm_vals)

    z = jnp.zeros_like(s)
    return mid._replace(
        nsk=_where_lead(sel, nsk, mid.nsk),
        nsv=_where_lead(sel, nsv, mid.nsv),
        new_len=jnp.where(sel, new_len, mid.new_len).astype(_I32),
        rm_keys=rm_keys, rm_vals=rm_vals,
        n_upc=jnp.where(sel, n_upc, z),
        n_rm_seq=jnp.where(sel, n_rm_seq, z),
        n_addseq=jnp.where(sel, n_addseq, z),
        spilled=jnp.where(sel & (spill_cnt > 0), 1, 0).astype(_I32),
        pending=p._replace(
            pend_k=_where_lead(sel, pk, p.pend_k),
            pend_v=_where_lead(sel, pv, p.pend_v),
            need_scatter=p.need_scatter | (sel & (spill_cnt > 0)),
            move_off=(mid.n_imm + jnp.where(sel, s, z)).astype(_I32)))


@jax.named_scope(obs.PQ_SCATTER)
def _pass_scatter(cfg: PQConfig, mid: TickMid) -> TickMid:
    """Step 5 as a separable pass: SL::addPar() segment-append of the
    par-bound batch, resolving the rebalance predicate.  Lanes whose
    batch is empty (everything eliminated or combined) skip untouched —
    `need_rebal` stays False for them."""
    p = mid.pending
    par_app, overflow = _scatter_fast(cfg, mid.par, p.pend_k, p.pend_v)
    sel = p.need_scatter
    return mid._replace(
        par=_select_tree(sel & ~overflow, par_app, mid.par),
        pending=p._replace(need_rebal=sel & overflow))


@jax.named_scope(obs.PQ_PREDS)
def _tick_preds(cfg: PQConfig, mid: TickMid) -> TickMid:
    """Steps 6–8 predicates: moveHead shortfall, adaptive detach policy
    (paper §2.1, N=1000 / M=100 / [8, 65536]), chopHead quiet counter.
    Pure elementwise bookkeeping — runs unconditionally."""
    p = mid.pending
    r2 = mid.rm_count - p.move_off      # removes that drained the merge
    # the parallel count INCLUDING this tick's batch — appended already,
    # or still pending the rebalance repair (same-tick servability)
    n_pend = jnp.sum(p.pend_k < INF, axis=-1, dtype=_I32)
    count_eff = mid.par.par_count + jnp.where(p.need_rebal, n_pend, 0)
    need_move = (r2 > 0) & (count_eff > 0)

    ins = mid.ins_since_move + mid.n_addseq
    new_detach = update_detach(cfg, p.detach_arg, ins)
    detach_n = jnp.where(need_move, new_detach, p.detach_arg)
    ins_since_move = jnp.where(need_move, 0, ins).astype(_I32)

    quiet = jnp.where(mid.rm_count > 0, 0, mid.quiet + 1).astype(_I32)
    need_chop = (quiet >= cfg.chop_patience) & (mid.new_len > 0)
    quiet = jnp.where(need_chop, 0, quiet)
    return mid._replace(
        detach_n=detach_n, ins_since_move=ins_since_move, quiet=quiet,
        pending=p._replace(need_move=need_move, r2=r2,
                           need_chop=need_chop))


@jax.named_scope(obs.PQ_REPAIR_REBALANCE)
def _repair_rebalance(cfg: PQConfig, mid: TickMid) -> TickMid:
    """Bucket-overflow repair: flatten + rank-merge the pending batch +
    redistribute.  Serves lanes that need a rebalance but NOT a moveHead
    (those take the fused `_repair_rebal_move`); all other lanes keep
    their state bit-for-bit (per-lane select)."""
    par, p = mid.par, mid.pending
    fk, fv = flatten_parallel(cfg, par)
    allk, allv = rank_merge_kv(fk, fv, p.pend_k, p.pend_v)
    n_pend = jnp.sum(p.pend_k < INF, axis=-1, dtype=_I32)
    newpar, dropped = _redistribute(cfg, allk, allv,
                                    par.par_count + n_pend)
    sel = p.need_rebal & ~p.need_move
    return mid._replace(
        par=_select_tree(sel, newpar, par),
        n_drop_rep=mid.n_drop_rep + jnp.where(sel, dropped, 0))


@jax.named_scope(obs.PQ_REPAIR_MOVE)
def _repair_move(cfg: PQConfig, mid: TickMid) -> TickMid:
    """SL::moveHead() repair: selection-based extraction of the
    max(detach_n, r2) smallest parallel keys (DESIGN.md §6) — serves the
    shortfall prefix into the removed stream and detaches the rest as a
    fresh sequential part.  Serves lanes that need a moveHead but NOT a
    rebalance (those take the fused `_repair_rebal_move`)."""
    par, p = mid.par, mid.pending
    R, SC, K = cfg.r_max, cfg.seq_cap, cfg.move_k_max
    served = jnp.minimum(p.r2, par.par_count)
    k_extract = jnp.minimum(jnp.maximum(p.detach_arg, p.r2),
                            par.par_count)
    # the fresh head must fit the sequential part WITH next-tick slack:
    # capping at spill_threshold (not seq_cap — the seed silently lost
    # overflow past seq_cap) keeps seq_len <= spill_threshold invariant,
    # so next tick's merge (<= threshold + a_max <= seq_cap - r_max) and
    # its spill (<= a_max, the spill window width) can never lose keys
    k_extract = jnp.minimum(k_extract, served + cfg.spill_threshold)
    sel_k, sel_v, nbk, nbv, nbc = kops.extract_k_bucketed(
        par.buckets, par.bvals, par.bcounts, k_extract, K,
        splitters=par.splitters, backend=cfg.backend)

    # serve the shortfall: rm slots [move_off, move_off + served)
    lead = sel_k.shape[:-1]
    ridx = jnp.broadcast_to(jnp.arange(R, dtype=_I32), lead + (R,))
    rel = ridx - jnp.expand_dims(p.move_off, -1)
    sel = p.need_move & ~p.need_rebal
    in3 = ((rel >= 0) & (rel < jnp.expand_dims(served, -1))
           & jnp.expand_dims(sel, -1))
    src3 = jnp.clip(rel, 0, K - 1)
    rm_keys = jnp.where(in3, jnp.take_along_axis(sel_k, src3, axis=-1),
                        mid.rm_keys)
    rm_vals = jnp.where(in3, jnp.take_along_axis(sel_v, src3, axis=-1),
                        mid.rm_vals)

    # fresh sequential part = extracted window beyond the served prefix
    nlen = k_extract - served
    nsk2 = _take_window(sel_k, served, SC, INF)
    nsv2 = _take_window(sel_v, served, SC, EMPTY_VAL)
    in_new = jnp.broadcast_to(jnp.arange(SC, dtype=_I32),
                              lead + (SC,)) < jnp.expand_dims(nlen, -1)
    nsk2 = jnp.where(in_new, nsk2, INF)
    nsv2 = jnp.where(in_new, nsv2, EMPTY_VAL)
    # ranges and splitters survive an in-place extraction: no
    # redistribute, no drops
    slotg = jnp.arange(cfg.bucket_cap, dtype=_I32)
    npar_min = jnp.min(jnp.where(slotg < nbc[..., None], nbk, INF),
                       axis=(-2, -1))
    newpar = ParPart(nbk, nbv, nbc, par.splitters, npar_min,
                     par.par_count - k_extract)
    return mid._replace(
        par=_select_tree(sel, newpar, par),
        nsk=_where_lead(sel, nsk2, mid.nsk),
        nsv=_where_lead(sel, nsv2, mid.nsv),
        new_len=jnp.where(sel, nlen, mid.new_len).astype(_I32),
        rm_keys=rm_keys, rm_vals=rm_vals,
        n_rm_par=jnp.where(sel, served, mid.n_rm_par).astype(_I32))


@jax.named_scope(obs.PQ_REPAIR_REBAL_MOVE)
def _repair_rebal_move(cfg: PQConfig, mid: TickMid) -> TickMid:
    """Fused rebalance + moveHead for lanes that need BOTH (the common
    case of a drain-heavy tick: this tick's adds overflowed a bucket AND
    the removes outran the sequential part).

    Composing the two passes sequentially would redistribute the merged
    stream into buckets only to immediately re-flatten and extract from
    them.  But extraction from a just-redistributed store has a closed
    form on the merged stream itself: the k smallest ARE the stream
    prefix, the fresh head is the next window, and surviving bucket i
    holds stream ranks [max(i*per, k), min((i+1)*per, kept)) shifted to
    slot 0 — so one flatten + rank-merge + window gathers reproduces
    `_repair_rebalance` followed by `_repair_move` bit-for-bit at about
    half the cost (no intermediate store, no second runs-flatten).
    """
    par, p = mid.par, mid.pending
    R, SC = cfg.r_max, cfg.seq_cap
    nb, bc = cfg.n_buckets, cfg.bucket_cap
    fk, fv = flatten_parallel(cfg, par)
    allk, allv = rank_merge_kv(fk, fv, p.pend_k, p.pend_v)
    size = allk.shape[-1]
    lead = allk.shape[:-1]
    n_pend = jnp.sum(p.pend_k < INF, axis=-1, dtype=_I32)
    total = par.par_count + n_pend

    # _redistribute's geometry, without materializing the store
    per = jnp.clip((total + nb - 1) // jnp.asarray(nb, _I32), 1, bc)
    kept = jnp.minimum(total, nb * per)
    dropped = total - kept

    # move sizing against the post-rebalance count (== kept); the
    # spill_threshold clamp mirrors _repair_move (seq_len invariant)
    served = jnp.minimum(p.r2, kept)
    k_extract = jnp.minimum(jnp.maximum(p.detach_arg, p.r2), kept)
    k_extract = jnp.minimum(k_extract, served + cfg.spill_threshold)

    # removed stream patch: the served prefix of the merged stream
    ridx = jnp.broadcast_to(jnp.arange(R, dtype=_I32), lead + (R,))
    rel = ridx - jnp.expand_dims(p.move_off, -1)
    sel = p.need_rebal & p.need_move
    in3 = ((rel >= 0) & (rel < jnp.expand_dims(served, -1))
           & jnp.expand_dims(sel, -1))
    src3 = jnp.clip(rel, 0, size - 1)
    rm_keys = jnp.where(in3, jnp.take_along_axis(allk, src3, axis=-1),
                        mid.rm_keys)
    rm_vals = jnp.where(in3, jnp.take_along_axis(allv, src3, axis=-1),
                        mid.rm_vals)

    # fresh sequential part: stream window [served, k_extract)
    nlen = k_extract - served
    nsk2 = _take_window(allk, served, SC, INF)
    nsv2 = _take_window(allv, served, SC, EMPTY_VAL)
    in_new = jnp.broadcast_to(jnp.arange(SC, dtype=_I32),
                              lead + (SC,)) < jnp.expand_dims(nlen, -1)
    nsk2 = jnp.where(in_new, nsk2, INF)
    nsv2 = jnp.where(in_new, nsv2, EMPTY_VAL)

    # surviving store: bucket i keeps the shifted tail of its window
    rows = jnp.arange(nb, dtype=_I32)[:, None]
    slot = jnp.arange(bc, dtype=_I32)[None, :]
    per_b = per[..., None, None]
    start = jnp.maximum(rows * per_b,
                        k_extract[..., None, None])        # [..., nb, 1]
    end = jnp.minimum((rows + 1) * per_b, kept[..., None, None])
    cnt2 = jnp.clip(end - start, 0, per_b)
    live = slot < cnt2
    src = jnp.clip(start + slot, 0, size - 1).reshape(lead + (nb * bc,))
    gk = jnp.take_along_axis(allk, src, axis=-1).reshape(lead + (nb, bc))
    gv = jnp.take_along_axis(allv, src, axis=-1).reshape(lead + (nb, bc))
    nbk = jnp.where(live, gk, INF)
    nbv = jnp.where(live, gv, EMPTY_VAL)
    nbc = cnt2[..., 0].astype(_I32)

    # splitters are the redistribute's (pre-extraction) bucket minima
    sp_idx = jnp.arange(nb, dtype=_I32) * per[..., None]
    sp = jnp.take_along_axis(allk, jnp.clip(sp_idx, 0, size - 1), axis=-1)
    sp = jnp.where(sp_idx < kept[..., None], sp, INF)
    splitters = sp.at[..., 0].set(-INF)
    head_idx = jnp.expand_dims(jnp.clip(k_extract, 0, size - 1), -1)
    par_min = jnp.where(
        kept > k_extract,
        jnp.take_along_axis(allk, head_idx, axis=-1)[..., 0],
        jnp.asarray(INF, _F32))
    newpar = ParPart(nbk, nbv, nbc, splitters, par_min,
                     (kept - k_extract).astype(_I32))
    return mid._replace(
        par=_select_tree(sel, newpar, par),
        nsk=_where_lead(sel, nsk2, mid.nsk),
        nsv=_where_lead(sel, nsv2, mid.nsv),
        new_len=jnp.where(sel, nlen, mid.new_len).astype(_I32),
        rm_keys=rm_keys, rm_vals=rm_vals,
        n_rm_par=jnp.where(sel, served, mid.n_rm_par).astype(_I32),
        n_drop_rep=mid.n_drop_rep + jnp.where(sel, dropped, 0))


@jax.named_scope(obs.PQ_REPAIR_CHOP)
def _repair_chop(cfg: PQConfig, mid: TickMid) -> TickMid:
    """SL::chopHead() repair: rank-merge the sequential head back into
    the bucket store (both sides already sorted — no re-sort of the
    world) and redistribute."""
    par, p = mid.par, mid.pending
    fk, fv = flatten_parallel(cfg, par)
    allk, allv = rank_merge_kv(fk, fv, mid.nsk, mid.nsv)
    newpar, dropped = _redistribute(cfg, allk, allv,
                                    par.par_count + mid.new_len)
    sel = p.need_chop
    return mid._replace(
        par=_select_tree(sel, newpar, par),
        nsk=_where_lead(sel, jnp.full(mid.nsk.shape, INF, _F32), mid.nsk),
        nsv=_where_lead(sel, jnp.full(mid.nsv.shape, EMPTY_VAL, _I32),
                        mid.nsv),
        new_len=jnp.where(sel, 0, mid.new_len).astype(_I32),
        n_drop_rep=mid.n_drop_rep + jnp.where(sel, dropped, 0))


@jax.named_scope(obs.PQ_FINISH)
def _tick_finish(cfg: PQConfig, mid: TickMid) -> Tuple[PQState,
                                                       TickResult]:
    """Steps 9b–10: serve accounting, minValue/lastSeq, state assembly."""
    R, SC = cfg.r_max, cfg.seq_cap
    lead = mid.rm_keys.shape[:-1]
    ridx = jnp.broadcast_to(jnp.arange(R, dtype=_I32), lead + (R,))
    requested = ridx < jnp.expand_dims(mid.rm_count, -1)
    rm_served = requested & (mid.rm_keys < INF)
    n_empty = mid.rm_count - rm_served.sum(axis=-1, dtype=_I32)

    nsk, par = mid.nsk, mid.par
    seq_head = nsk[..., 0]
    tail_idx = jnp.expand_dims(jnp.clip(mid.new_len - 1, 0, SC - 1), -1)
    seq_tail = jnp.take_along_axis(nsk, tail_idx, axis=-1)[..., 0]
    last_seq = jnp.where(mid.new_len > 0, seq_tail, -INF)
    min_value = jnp.where(mid.new_len > 0, seq_head, par.par_min)

    st = mid.stats0
    p = mid.pending
    one = jnp.ones((), _I32)
    stats = PQStats(
        add_imm_elim=st.add_imm_elim + mid.n_imm,
        add_upc_elim=st.add_upc_elim + mid.n_upc,
        add_seq=st.add_seq + mid.n_addseq,
        add_par=st.add_par + mid.n_par_adds,
        rm_seq=st.rm_seq + mid.n_rm_seq,
        rm_par=st.rm_par + mid.n_rm_par,
        rm_empty=st.rm_empty + n_empty,
        n_movehead=st.n_movehead + p.need_move.astype(_I32),
        n_chophead=st.n_chophead + p.need_chop.astype(_I32),
        n_rebalance=st.n_rebalance + p.need_rebal.astype(_I32),
        n_spill=st.n_spill + mid.spilled,
        n_dropped=st.n_dropped + mid.n_drop_rep,
        n_ticks=st.n_ticks + one,
        n_removes=st.n_removes + mid.rm_count,
    )

    new_state = PQState(
        seq_keys=nsk, seq_vals=mid.nsv, seq_len=mid.new_len.astype(_I32),
        buckets=par.buckets, bvals=par.bvals, bcounts=par.bcounts,
        splitters=par.splitters, par_min=par.par_min,
        par_count=par.par_count,
        min_value=min_value, last_seq=last_seq,
        detach_n=mid.detach_n, ins_since_move=mid.ins_since_move,
        quiet_ticks=mid.quiet, stats=stats,
    )
    repairs = jnp.stack(
        [p.need_combine, p.need_scatter, p.need_rebal, p.need_move,
         p.need_chop], axis=-1).astype(_I32)
    return new_state, TickResult(mid.rm_keys, mid.rm_vals, rm_served,
                                 repairs)


def _tick_impl(cfg: PQConfig, state: PQState, add_keys, add_vals,
               add_mask, rm_count) -> Tuple[PQState, TickResult]:
    """head -> combine -> scatter -> predicates -> conditional repairs
    (rebalance+moveHead fused, rebalance-only, moveHead-only, chopHead)
    -> finish.  The combine/scatter passes run inline here (a lone queue
    nearly always needs them); each repair runs under its own lax.cond,
    so a tick pays only the rare paths it actually needs.

    With a pallas ``cfg.backend`` the hot pipeline (head through the
    moveHead repair) runs as the L=1 case of the lanes-in-grid
    megakernel (kernels/lane_tick.py) — same passes, same bits, one
    kernel launch — and only the rare repairs keep their conds here."""
    if cfg.backend.is_pallas:
        from repro.kernels import lane_tick as _lt   # lazy: import cycle
        mid = _lt.fused_tick_mid(
            cfg, jax.tree.map(lambda x: x[None], state),
            add_keys[None], add_vals[None], add_mask[None],
            jnp.asarray(rm_count, _I32)[None])
        mid = jax.tree.map(lambda x: x[0], mid)
        repairs = (
            (mid.pending.need_rebal & mid.pending.need_move,
             _repair_rebal_move),
            (mid.pending.need_rebal & ~mid.pending.need_move,
             _repair_rebalance),
            (mid.pending.need_chop, _repair_chop),
        )
    else:
        mid = _tick_head(cfg, state, add_keys, add_vals, add_mask,
                         rm_count)
        mid = _pass_combine(cfg, mid)
        mid = _pass_scatter(cfg, mid)
        mid = _tick_preds(cfg, mid)
        p = mid.pending
        repairs = (
            (p.need_rebal & p.need_move, _repair_rebal_move),
            (p.need_rebal & ~p.need_move, _repair_rebalance),
            (p.need_move & ~p.need_rebal, _repair_move),
            (p.need_chop, _repair_chop),
        )
    with jax.named_scope(obs.PQ_PREDS):     # the repairs' dispatch
        for pred, repair in repairs:
            mid = jax.lax.cond(pred, functools.partial(repair, cfg),
                               lambda m: m, mid)
    return _tick_finish(cfg, mid)


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def tick(cfg: PQConfig, state: PQState, add_keys, add_vals, add_mask,
         rm_count) -> Tuple[PQState, TickResult]:
    """One combined round over an operation batch.

    Args:
      cfg: static PQConfig.
      state: current PQState.  DONATED — its buffers are reused for the
        new state; do not touch the argument after the call.
      add_keys: [a_max] f32 — keys of PQ::add() requests (finite).
      add_vals: [a_max] i32 — payloads.
      add_mask: [a_max] bool — which slots hold real adds.
      rm_count: scalar i32 — number of PQ::removeMin() requests (<= r_max).

    Returns (new_state, TickResult).
    """
    return _tick_impl(cfg, state, add_keys, add_vals, add_mask, rm_count)


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def tick_n(cfg: PQConfig, state: PQState, add_keys, add_vals, add_mask,
           rm_counts) -> Tuple[PQState, TickResult]:
    """`lax.scan` multi-tick driver: T ticks in one dispatch.

    Args are the per-tick arrays stacked on a leading time axis
    (add_keys [T, a_max], ..., rm_counts [T]); `state` is DONATED.
    Returns (final state, TickResult stacked [T, ...]).  At ~ms-scale
    ticks the per-call dispatch/transfer overhead is a measurable
    fraction of the budget; scanning amortizes it to one call.
    """
    def body(s, xs):
        return _tick_impl(cfg, s, *xs)

    return jax.lax.scan(body, state,
                        (add_keys, add_vals, add_mask, rm_counts))


# ---------------------------------------------------------------------------
# convenience wrappers
# ---------------------------------------------------------------------------

def size(state: PQState) -> jnp.ndarray:
    return state.seq_len + state.par_count


def peek_min(state: PQState) -> jnp.ndarray:
    return state.min_value


def resident(cfg: PQConfig, state: PQState):
    """Enumerate every resident element of the queue.

    Returns ``(keys [cap], vals [cap], live [cap])`` with cap =
    seq_cap + n_buckets * bucket_cap: the sequential part is its dense
    sorted prefix (``seq_len``), the parallel part is every finite
    bucket slot (INF = empty by the bucket invariant).  The single-queue
    twin of :func:`repro.core.sharded.resident` — the drain half of the
    adaptive controller's engine switch (core/adaptive.py)."""
    live_seq = jnp.arange(cfg.seq_cap, dtype=_I32) < state.seq_len
    bk = state.buckets.reshape(-1)
    bv = state.bvals.reshape(-1)
    keys = jnp.concatenate([state.seq_keys, bk])
    vals = jnp.concatenate([state.seq_vals, bv])
    live = jnp.concatenate([live_seq, jnp.isfinite(bk)])
    return keys, vals, live


def add_batch(cfg: PQConfig, state: PQState, keys, vals=None):
    """Insert-only tick (pads/masks to a_max)."""
    n = keys.shape[0]
    if n > cfg.a_max:
        raise ValueError(f"batch of {n} adds > a_max={cfg.a_max}")
    if vals is None:
        vals = jnp.arange(n, dtype=_I32)
    ak = jnp.full((cfg.a_max,), 0.0, _F32).at[:n].set(keys.astype(_F32))
    av = jnp.full((cfg.a_max,), EMPTY_VAL, _I32).at[:n].set(vals.astype(_I32))
    mask = jnp.zeros((cfg.a_max,), bool).at[:n].set(True)
    new_state, _ = tick(cfg, state, ak, av, mask, jnp.zeros((), _I32))
    return new_state


def remove_batch(cfg: PQConfig, state: PQState, count):
    """Remove-only tick."""
    ak = jnp.full((cfg.a_max,), INF, _F32)
    av = jnp.full((cfg.a_max,), EMPTY_VAL, _I32)
    mask = jnp.zeros((cfg.a_max,), bool)
    return tick(cfg, state, ak, av, mask, jnp.asarray(count, _I32))
