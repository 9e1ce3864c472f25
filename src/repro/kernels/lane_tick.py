"""Fused lanes-in-grid tick megakernel (the hot lane pipeline as ONE
``pallas_call``).

The sharded queue's jnp path runs each tick as a ``vmap`` of the
unconditional head plus a chain of batch-level XLA ops (core/sharded.py,
``_lanes_tick``); every pass boundary is an HBM round-trip of the whole
lane state.  Here the L-lanes axis maps onto the Pallas GRID instead: one
kernel launch executes, per grid step = per lane, the entire hot pipeline

    ``_tick_head`` (sanitize / immediate elimination / small-large split)
    -> ``_pass_combine`` (rank merge + consume + spill)
    -> ``_pass_scatter`` (bucket segment-append)
    -> ``_tick_preds``  (moveHead / chopHead predicates)
    -> ``_repair_move`` (the common moveHead repair, per-lane selected)

on VMEM-resident blocks, so one lane's tick touches HBM exactly twice
(state in, state out).  The three RARE repairs (rebalance, fused
rebalance+move, chop) and ``_tick_finish`` stay OUTSIDE under the same
any-lane ``lax.cond`` hoists as the jnp path — they fire on a small
minority of ticks and need the full flatten/redistribute machinery.

Bit-equivalence by construction: the kernel body executes the SAME pass
functions as the reference (cfg forced to the jnp backend), under
``ops.kernel_safe_primitives()`` which swaps only two helpers for exact
kernel-lowerable twins (compare-all searchsorted, stable lexicographic
bitonic argsort).  Every pass is a per-lane select whose unselected
branch is a bit-exact identity, so running a pass unconditionally inside
the kernel equals the reference's cond-hoisted skip.  CI pins
``pallas_interpret`` equality against the jnp lane tick across the full
repair matrix (tests/test_lane_megakernel.py).

Mosaic refuses this kernel for v5e (DESIGN.md §13), so it runs only in
interpret mode and no engine reaches it by default: at L>1 the
``(1, n)`` lane blocks are not (8, 128)-aligned (:func:`_lane_spec`),
and at L=1 the ``take_along_axis`` in ``pqueue._shift_left`` is a
gather Mosaic does not lower ("Only 2D gather is supported").  The
per-op kernels (bitonic / merge_consume / radix_select) are refused
too.  tests/test_tpu_compile.py pins each refusal as a strict xfail.

Import note: this module imports ``repro.core.pqueue`` and is therefore
imported LAZILY by core/pqueue.py + core/sharded.py (and deliberately not
re-exported from repro.kernels) to avoid an import cycle.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import pqueue
from repro.core.config import EMPTY_VAL
from repro.kernels import ops as kops

INF = jnp.inf
_I32 = jnp.int32
_F32 = jnp.float32

#: number of kernel inputs (per-lane blocks), in _mid_kernel ref order
_N_IN = 18


def _out_layout(cfg):
    """Ordered (per-lane shape, dtype) of every kernel output — the
    TickMid fields the outside repairs + finish consume.  Scalars are
    (1,)-wide blocks; predicates ride as i32 (Pallas memories are
    numeric) and are re-boolled outside."""
    sc, a, r = cfg.seq_cap, cfg.a_max, cfg.r_max
    nb, bc = cfg.n_buckets, cfg.bucket_cap
    f, i = _F32, _I32
    return ([((sc,), f), ((sc,), i), ((), i),              # nsk nsv new_len
             ((nb, bc), f), ((nb, bc), i), ((nb,), i),     # par store
             ((nb,), f), ((), f), ((), i),                 # splitters/min/count
             ((r,), f), ((r,), i), ((), i),                # rm stream + count
             ((a,), f), ((a,), i)]                         # pend_k pend_v
            + [((), i)] * 19)                              # preds + counters


def _mid_kernel(cfg, *refs):
    """One lane's hot tick: rebuild the lane PQState from the grid-step
    blocks, run the pass chain (cfg's backend is forced to jnp — we are
    already inside the kernel), write the mid fields back."""
    (skr, svr, slr, bkr, bvr, bcr, spr, pmr, pcr, mvr, lsr, dnr, imr,
     qtr, akr, avr, amr, grr) = refs[:_N_IN]
    outs = refs[_N_IN:]
    zero = jnp.zeros((), _I32)
    state = pqueue.PQState(
        seq_keys=skr[0], seq_vals=svr[0], seq_len=slr[0, 0],
        buckets=bkr[0], bvals=bvr[0], bcounts=bcr[0], splitters=spr[0],
        par_min=pmr[0, 0], par_count=pcr[0, 0],
        min_value=mvr[0, 0], last_seq=lsr[0, 0],
        detach_n=dnr[0, 0], ins_since_move=imr[0, 0],
        quiet_ticks=qtr[0, 0],
        # stats ride through the tick untouched until _tick_finish, which
        # runs outside — the wrapper re-attaches the real stats0
        stats=pqueue.PQStats(*([zero] * len(pqueue.PQStats._fields))))
    mid = pqueue._tick_head(cfg, state, akr[0], avr[0], amr[0] != 0,
                            grr[0, 0], adds_sorted=True)
    mid = pqueue._pass_combine(cfg, mid)
    mid = pqueue._pass_scatter(cfg, mid)
    mid = pqueue._tick_preds(cfg, mid)
    mid = pqueue._repair_move(cfg, mid)

    p, par = mid.pending, mid.par
    vals = (mid.nsk, mid.nsv, mid.new_len,
            par.buckets, par.bvals, par.bcounts, par.splitters,
            par.par_min, par.par_count,
            mid.rm_keys, mid.rm_vals, mid.rm_count,
            p.pend_k, p.pend_v,
            p.need_combine, p.need_scatter, p.need_rebal, p.need_move,
            p.r2, p.move_off, p.detach_arg, p.need_chop,
            mid.n_imm, mid.n_upc, mid.n_rm_seq, mid.n_addseq,
            mid.n_par_adds, mid.spilled, mid.n_rm_par, mid.n_drop_rep,
            mid.detach_n, mid.ins_since_move, mid.quiet)
    for ref, val in zip(outs, vals):
        v = jnp.asarray(val)
        if v.ndim == 0:
            ref[0, 0] = v.astype(ref.dtype)
        else:
            ref[0] = v.astype(ref.dtype)


def _lane_spec(shape):
    """BlockSpec mapping grid step l to lane l's block of a [L, ...] array."""
    if len(shape) == 2:
        return pl.BlockSpec((1, shape[1]), lambda l: (l, 0))
    return pl.BlockSpec((1,) + tuple(shape[1:]), lambda l: (l, 0, 0))


def fused_tick_mid(cfg, lanes: pqueue.PQState, lk, lv, lm, grants, *,
                   adds_sorted: bool = False) -> pqueue.TickMid:
    """Run the hot tick of every lane through ONE lanes-in-grid
    ``pallas_call`` and return the lane-batched :class:`pqueue.TickMid`
    (rare repairs still pending — callers hoist them exactly like the
    jnp path, then ``_tick_finish``).

    Args mirror ``sharded._lanes_tick``: ``lanes`` is a [L, ...]-stacked
    PQState, ``lk/lv/lm`` the routed [L, a_max] add batch, ``grants``
    the per-lane [L] removeMin allocation.  ``cfg.backend`` must be a
    pallas :class:`~repro.kernels.ops.KernelBackend`; its ``interpret``
    flag (resolved once at config construction) picks Mosaic vs the
    interpreter.
    """
    bk = cfg.backend
    if not getattr(bk, "is_pallas", False):
        raise ValueError(
            f"fused_tick_mid needs a pallas KernelBackend, got {bk!r}")
    L = lk.shape[0]
    A = cfg.a_max

    if adds_sorted:
        ak, av, am = lk, lv, lm
    else:
        # hoist the head's a_max-wide batch sort out of the kernel: the
        # kernel then runs the adds_sorted=True head, bit-identical to
        # sorting in-head because this IS the head's sanitize + stable
        # sort, and the prefix mask re-sanitizes to the same arrays
        sk = jnp.where(lm, lk.astype(_F32), INF)
        sv = jnp.where(lm, lv.astype(_I32), EMPTY_VAL)
        ak, av, _ = kops.sort_kvf(sk, sv, jnp.zeros(sk.shape, _I32),
                                  backend=kops.KernelBackend("jnp"))
        am = (jnp.arange(A, dtype=_I32)[None, :]
              < lm.sum(axis=-1, dtype=_I32)[:, None])

    col = lambda x, dt: jnp.asarray(x, dt).reshape(L, 1)    # noqa: E731
    inputs = [
        lanes.seq_keys.astype(_F32), lanes.seq_vals.astype(_I32),
        col(lanes.seq_len, _I32),
        lanes.buckets.astype(_F32), lanes.bvals.astype(_I32),
        lanes.bcounts.astype(_I32), lanes.splitters.astype(_F32),
        col(lanes.par_min, _F32), col(lanes.par_count, _I32),
        col(lanes.min_value, _F32), col(lanes.last_seq, _F32),
        col(lanes.detach_n, _I32), col(lanes.ins_since_move, _I32),
        col(lanes.quiet_ticks, _I32),
        ak.astype(_F32), av.astype(_I32), am.astype(_I32),
        col(grants, _I32),
    ]
    layout = _out_layout(cfg)
    out_shape = [jax.ShapeDtypeStruct((L,) + (s if s else (1,)), d)
                 for s, d in layout]
    # the kernel body (the whole pqueue pass chain) is traced HERE, so
    # the kernel-safe primitive swap wraps the pallas_call invocation
    with kops.kernel_safe_primitives():
        outs = pl.pallas_call(
            functools.partial(_mid_kernel,
                              dataclasses.replace(cfg, backend="jnp")),
            grid=(L,),
            in_specs=[_lane_spec(x.shape) for x in inputs],
            out_specs=[_lane_spec(o.shape) for o in out_shape],
            out_shape=out_shape,
            interpret=bk.interpret,
        )(*inputs)

    (nsk, nsv, new_len, pbk, pbv, pbc, psp, pmin, pcnt, rmk, rmv, rmc,
     pendk, pendv, nc, ns, nr, nm, r2, mo, da, nchop, n_imm, n_upc,
     n_rm_seq, n_addseq, n_par_adds, spilled, n_rm_par, n_drop_rep,
     detach_n, ins_since_move, quiet) = outs
    s1 = lambda x: x[..., 0]                                # noqa: E731
    b1 = lambda x: x[..., 0] != 0                           # noqa: E731
    # small_*/large_* are dead past the combine pass (only pend_* feeds
    # the rare repairs), so they alias pend_* instead of riding out of
    # the kernel as four more [L, a_max] HBM writes
    pending = pqueue.RepairPending(
        need_combine=b1(nc), small_k=pendk, small_v=pendv,
        large_k=pendk, large_v=pendv,
        need_scatter=b1(ns), pend_k=pendk, pend_v=pendv,
        need_rebal=b1(nr), need_move=b1(nm), r2=s1(r2), move_off=s1(mo),
        detach_arg=s1(da), need_chop=b1(nchop))
    return pqueue.TickMid(
        nsk=nsk, nsv=nsv, new_len=s1(new_len),
        par=pqueue.ParPart(pbk, pbv, pbc, psp, s1(pmin), s1(pcnt)),
        rm_keys=rmk, rm_vals=rmv, rm_count=s1(rmc), pending=pending,
        n_imm=s1(n_imm), n_upc=s1(n_upc), n_rm_seq=s1(n_rm_seq),
        n_addseq=s1(n_addseq), n_par_adds=s1(n_par_adds),
        spilled=s1(spilled), n_rm_par=s1(n_rm_par),
        n_drop_rep=s1(n_drop_rep), detach_n=s1(detach_n),
        ins_since_move=s1(ins_since_move), quiet=s1(quiet),
        stats0=lanes.stats)
