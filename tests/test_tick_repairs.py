"""Lane-native vs vmapped equivalence + buffer-donation semantics.

The sharded queue's fused lane-major tick (repair passes hoisted out of
the vmap behind batch-level `lax.cond`s, kernels running all lanes
through one leading-axis call) must produce BIT-IDENTICAL states and
results to the reference realization — routing each lane its slot-order
batch and running `jax.vmap(pqueue.tick)`, whose cond→select lowering
executes every pass on every lane and per-lane-selects the outcome.
The workloads here are arranged so every separable pass (combine,
scatter, rebalance, moveHead, chopHead) fires at least once.

Also pinned: `tick`/`tick_n` donate their state argument — chaining on
the RETURNED state must work and change nothing vs undonated use, and
the scan driver must match eager tick-by-tick evolution exactly.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import EMPTY_VAL, PQConfig
from repro.core import pqueue
from repro.core import sharded as shq
from repro.core.factory import EngineSpec, make_engine

W = 64
# tiny bucket_cap so adds overflow a bucket (rebalance); small detach
# bounds and chop_patience so moveHead/chopHead trigger quickly
BASE = PQConfig(a_max=W, r_max=W, seq_cap=512, n_buckets=4, bucket_cap=8,
                detach_min=4, detach_max=64, detach_init=8,
                chop_patience=3)


def _scfg(lanes, **kw):
    return make_engine(EngineSpec(engine="sharded", width=W,
                                  base=BASE, lanes=lanes, **kw)).cfg


def _batch(keys, vals, w):
    ak = np.full((w,), np.inf, np.float32)
    av = np.full((w,), EMPTY_VAL, np.int32)
    mask = np.zeros((w,), bool)
    ak[:len(keys)] = keys
    av[:len(keys)] = vals
    mask[:len(keys)] = True
    return jnp.asarray(ak), jnp.asarray(av), jnp.asarray(mask)


def _assert_trees_equal(a, b, what):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=what)


@pytest.mark.parametrize("lanes", [2, 4])
def test_fused_lane_tick_matches_vmapped_reference(lanes):
    # preroute forced OFF: the hand-built reference path below feeds the
    # lanes the FULL batch and rm_count, so any pre-route match inside
    # shq.tick would (correctly) diverge from it — the pre-route layer
    # has its own equivalence/conservation suite in tests/test_preroute.py
    cfg = _scfg(lanes, preroute="off")
    lc = cfg.lane
    state = shq.init(cfg, seed=7)
    rng = np.random.default_rng(11)

    ref_tick = jax.vmap(
        lambda s, k, v, m, r: pqueue.tick(lc, s, k, v, m, r))
    # jitted, as sharded.tick runs it: called eagerly, each lax.cond in
    # the lane tick traces and compiles its branches again on every call
    # and the process keeps each program's code mapped
    lanes_tick = jax.jit(functools.partial(shq._lanes_tick, lc,
                                           adds_sorted=True))

    fired = np.zeros(5, np.int64)   # combine, scatter, rebal, move, chop
    next_val = 0
    for t in range(48):
        # phased workload: pile up adds (scatter + rebalance); then
        # either a big drain (moveHead serves everything) or a FEW
        # removes (moveHead detaches a head bigger than it serves);
        # then quiet ticks so the surviving head chops back
        cycle, phase = t // 12, t % 12
        if phase < 4:
            n_add, n_rm = int(rng.integers(W // 2, W + 1)), 0
        elif phase == 4:
            n_add = 0
            n_rm = W if cycle % 2 else int(rng.integers(1, 5))
        else:
            n_add, n_rm = 0, 0
        keys = np.round(rng.uniform(0, 1000, n_add), 3).astype(np.float32)
        vals = np.arange(next_val, next_val + n_add, dtype=np.int32)
        next_val += n_add
        ak, av, mask = _batch(keys, vals, W)
        rm = jnp.asarray(n_rm, jnp.int32)

        # tick() donates: keep an undonated copy of the pre-state
        pre = jax.tree.map(jnp.copy, state)
        state, _ = shq.tick(cfg, state, ak, av, mask, rm)

        # fused lane-major path on the identical inputs
        lk_s, lv_s, lm_s, _ = shq._route_adds_sorted(
            cfg, state.route_inv, ak, av, mask)
        grants = shq._alloc_removes(cfg, pre.lanes, rm,
                                    incoming=lm_s.sum(-1, dtype=jnp.int32))
        lanes_f, res_f, _ = lanes_tick(pre.lanes, lk_s, lv_s, lm_s, grants)

        # reference: slot-order routing, every lane a full vmapped tick
        lk_r, lv_r, lm_r, _ = shq._route_adds(cfg, state.route, ak, av,
                                              mask)
        lanes_r, res_r = ref_tick(jax.tree.map(jnp.copy, pre.lanes),
                                  lk_r, lv_r, lm_r, grants)

        _assert_trees_equal(lanes_f, lanes_r, f"tick {t}: lane states")
        _assert_trees_equal(res_f, res_r, f"tick {t}: lane results")
        # and the public sharded tick took exactly the fused path
        _assert_trees_equal(lanes_f, state.lanes,
                            f"tick {t}: sharded.tick internal")
        fired += np.asarray(res_f.repairs).sum(axis=0)

    assert (fired > 0).all(), (
        f"workload never triggered every pass "
        f"(combine,scatter,rebal,move,chop fired {fired.tolist()})")


def test_tick_donation_chain_matches_fresh_states():
    cfg = BASE
    rng = np.random.default_rng(3)
    ticks = []
    next_val = 0
    for _ in range(12):
        n_add = int(rng.integers(0, W + 1))
        keys = rng.uniform(0, 100, n_add).astype(np.float32)
        vals = np.arange(next_val, next_val + n_add, dtype=np.int32)
        next_val += n_add
        ticks.append(_batch(keys, vals, W)
                     + (jnp.asarray(int(rng.integers(0, W)), jnp.int32),))

    # chained use of the donated API: each call consumes the previous
    # call's output — must not crash on re-use of the chain
    s_chain = pqueue.init(cfg)
    chain_out = []
    for ak, av, mask, rm in ticks:
        s_chain, res = pqueue.tick(cfg, s_chain, ak, av, mask, rm)
        chain_out.append(np.asarray(res.rm_keys))

    # same ticks with a donation-proof copy at every step
    s_copy = pqueue.init(cfg)
    for (ak, av, mask, rm), got in zip(ticks, chain_out):
        s_copy, res = pqueue.tick(cfg, jax.tree.map(jnp.copy, s_copy),
                                  ak, av, mask, rm)
        np.testing.assert_array_equal(got, np.asarray(res.rm_keys))
    _assert_trees_equal(s_chain, s_copy, "chained vs copied states")


def test_tick_n_matches_eager_ticks():
    cfg = BASE
    rng = np.random.default_rng(5)
    T = 10
    aks, avs, masks, rms = [], [], [], []
    next_val = 0
    for _ in range(T):
        n_add = int(rng.integers(0, W + 1))
        keys = rng.uniform(0, 100, n_add).astype(np.float32)
        ak, av, mask = _batch(keys,
                              np.arange(next_val, next_val + n_add,
                                        dtype=np.int32), W)
        next_val += n_add
        aks.append(ak); avs.append(av); masks.append(mask)
        rms.append(int(rng.integers(0, W)))

    s_eager = pqueue.init(cfg)
    eager_res = []
    for i in range(T):
        s_eager, res = pqueue.tick(cfg, s_eager, aks[i], avs[i], masks[i],
                                   jnp.asarray(rms[i], jnp.int32))
        eager_res.append(res)

    s_scan, res_n = pqueue.tick_n(
        cfg, pqueue.init(cfg), jnp.stack(aks), jnp.stack(avs),
        jnp.stack(masks), jnp.asarray(rms, jnp.int32))
    _assert_trees_equal(s_scan, s_eager, "tick_n final state")
    for i in range(T):
        np.testing.assert_array_equal(np.asarray(res_n.rm_keys[i]),
                                      np.asarray(eager_res[i].rm_keys))
        np.testing.assert_array_equal(np.asarray(res_n.rm_served[i]),
                                      np.asarray(eager_res[i].rm_served))


def test_sharded_tick_n_matches_eager_ticks():
    cfg = _scfg(4)
    rng = np.random.default_rng(9)
    T = 8
    aks, avs, masks, rms = [], [], [], []
    next_val = 0
    for _ in range(T):
        n_add = int(rng.integers(0, W + 1))
        keys = rng.uniform(0, 100, n_add).astype(np.float32)
        ak, av, mask = _batch(keys,
                              np.arange(next_val, next_val + n_add,
                                        dtype=np.int32), W)
        next_val += n_add
        aks.append(ak); avs.append(av); masks.append(mask)
        rms.append(int(rng.integers(0, W)))

    s_eager = shq.init(cfg, seed=2)
    eager = []
    for i in range(T):
        s_eager, res = shq.tick(cfg, s_eager, aks[i], avs[i], masks[i],
                                jnp.asarray(rms[i], jnp.int32))
        eager.append(res)

    s_scan, res_n = shq.tick_n(
        cfg, shq.init(cfg, seed=2), jnp.stack(aks), jnp.stack(avs),
        jnp.stack(masks), jnp.asarray(rms, jnp.int32))
    _assert_trees_equal(s_scan, s_eager, "sharded tick_n final state")
    for i in range(T):
        np.testing.assert_array_equal(np.asarray(res_n.rm_keys[i]),
                                      np.asarray(eager[i].rm_keys))
