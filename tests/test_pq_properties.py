"""Property tests: the batched PQ against its sequential specification.

The central contract (DESIGN.md §2): a tick with adds X and r removes
returns exactly the r smallest keys of PQ ∪ X (multiset), and the
post-state holds the rest.  This is the batch-sequential equivalent of the
paper's linearizability argument, checked for pqe and both baselines.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (EMPTY_VAL, FCPQ, ParallelPQ, PQConfig, RefPQ, init,
                        tick)
from repro.core.pqueue import PQState, _take_window

CFG = PQConfig(a_max=32, r_max=32, seq_cap=256, n_buckets=8, bucket_cap=32,
               detach_min=4, detach_max=64, detach_init=8, chop_patience=8)
TINY = PQConfig(a_max=16, r_max=16, seq_cap=64, n_buckets=4, bucket_cap=16,
                detach_min=2, detach_max=32, detach_init=4, chop_patience=4)


def drive(cfg, impl_init, impl_tick, ops, check_size=True):
    """ops: list of (keys list, rm_count). Asserts oracle agreement."""
    state = impl_init(cfg)
    ref = RefPQ()
    next_val = 0
    for keys, n_rm in ops:
        keys = keys[:max(0, min(len(keys),
                                cfg.par_cap - len(ref), cfg.a_max))]
        ak = np.full((cfg.a_max,), np.inf, np.float32)
        av = np.full((cfg.a_max,), EMPTY_VAL, np.int32)
        mask = np.zeros((cfg.a_max,), bool)
        for i, k in enumerate(keys):
            ak[i], av[i], mask[i] = k, next_val + i, True
        next_val += len(keys)
        state, res = impl_tick(cfg, state, jnp.asarray(ak), jnp.asarray(av),
                               jnp.asarray(mask), jnp.asarray(n_rm))
        got = np.sort(np.asarray(res.rm_keys)[np.asarray(res.rm_served)])
        exp = np.sort(np.array(
            [k for k, _ in ref.tick(keys, range(len(keys)), n_rm)
             if k != np.inf], np.float32))
        np.testing.assert_allclose(got, exp, rtol=0, atol=0)
        if check_size:
            assert _size(state) == len(ref)
    return state


def _size(state):
    if isinstance(state, PQState):
        return int(state.seq_len) + int(state.par_count)
    if hasattr(state, "length"):
        return int(state.length)
    return int(state.par.par_count)


key_lists = st.lists(
    st.floats(min_value=0.0, max_value=1e4, allow_nan=False, width=32),
    min_size=0, max_size=16)
op_seqs = st.lists(st.tuples(key_lists, st.integers(0, 16)), min_size=1,
                   max_size=25)


@given(op_seqs)
def test_pqe_matches_oracle(ops):
    drive(TINY, init, tick, ops)


@given(op_seqs)
def test_fc_baseline_matches_oracle(ops):
    drive(TINY, FCPQ.init, FCPQ.tick, ops)


@given(op_seqs)
def test_parallel_baseline_matches_oracle(ops):
    drive(TINY, ParallelPQ.init, ParallelPQ.tick, ops)


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10)
def test_pqe_random_mixes(seed):
    rng = np.random.default_rng(seed)
    ops = [(rng.uniform(0, 1000, rng.integers(0, CFG.a_max + 1)).tolist(),
            int(rng.integers(0, CFG.r_max + 1)))
           for _ in range(30)]
    drive(CFG, init, tick, ops)


def test_duplicate_keys_conserved():
    """Multiset conservation with heavy key collisions."""
    ops = [([5.0] * 16, 0), ([5.0] * 8 + [1.0] * 4, 10), ([], 16), ([], 16)]
    drive(TINY, init, tick, ops)


def test_movehead_serves_same_tick_parallel_adds():
    """Regression: with an EMPTY parallel part, a tick whose adds scatter
    into the buckets (key > lastSeq) and whose removes exceed the
    sequential part must still serve from those same-tick adds (the
    moveHead gate must look at the post-scatter count, not the pre-tick
    one)."""
    ops = [
        ([0.0, 1.0, 2.0, 3.0], 0),   # all 4 adds scatter to the par part
        ([], 1),                     # moveHead drains par fully: seq=[1,2,3]
        ([100.0], 4),                # par add + removes past the seq part
        ([], 4),                     # drain the rest
    ]
    drive(TINY, init, tick, ops)


def test_empty_removes_return_sentinel():
    state = init(TINY)
    ak = jnp.full((TINY.a_max,), jnp.inf, jnp.float32)
    av = jnp.full((TINY.a_max,), EMPTY_VAL, jnp.int32)
    mask = jnp.zeros((TINY.a_max,), bool)
    state, res = tick(TINY, state, ak, av, mask, jnp.asarray(5))
    assert int(res.rm_served.sum()) == 0
    assert int(state.stats.rm_empty) == 5  # paper Alg.3 line 2: MaxInt


def test_adaptive_detach_bounds_and_policy():
    """The paper's halve/double policy: bounds respected, doubling on
    quiet sequential parts, halving under addSeq pressure."""
    from repro.core.adaptive import update_detach
    cfg = CFG
    # doubling below M
    assert int(update_detach(cfg, jnp.asarray(8), jnp.asarray(0))) == 16
    # halving above N
    assert int(update_detach(cfg, jnp.asarray(8),
                             jnp.asarray(cfg.halve_threshold + 1))) == 4
    # clamped at bounds
    assert int(update_detach(cfg, jnp.asarray(cfg.detach_max),
                             jnp.asarray(0))) == cfg.detach_max
    assert int(update_detach(cfg, jnp.asarray(cfg.detach_min),
                             jnp.asarray(10 ** 6))) == cfg.detach_min


def test_detach_adapts_in_state():
    """moveHead events actually move detach_n (integration of the policy)."""
    state = init(TINY)
    rng = np.random.default_rng(3)
    seen = set()
    ref_len = 0
    for t in range(50):
        n_add = int(rng.integers(0, TINY.a_max + 1))
        n_add = min(n_add, TINY.par_cap - ref_len)
        keys = rng.uniform(0, 100, n_add).astype(np.float32)
        ak = np.full((TINY.a_max,), np.inf, np.float32)
        av = np.zeros((TINY.a_max,), np.int32)
        mask = np.zeros((TINY.a_max,), bool)
        ak[:n_add] = keys
        mask[:n_add] = True
        n_rm = int(rng.integers(0, TINY.r_max + 1))
        state, res = tick(TINY, state, jnp.asarray(ak), jnp.asarray(av),
                          jnp.asarray(mask), jnp.asarray(n_rm))
        ref_len += n_add - int(res.rm_served.sum())
        seen.add(int(state.detach_n))
        assert TINY.detach_min <= int(state.detach_n) <= TINY.detach_max
    assert len(seen) > 1, "detach size never adapted"


def test_chophead_fires_on_quiet_stream():
    """chopHead folds the sequential part back after quiet ticks."""
    state = init(TINY)
    # build a sequential part by removing (forces moveHead)
    state = _add(state, np.arange(16, dtype=np.float32))
    state = _add(state, np.arange(16, 32, dtype=np.float32))
    state, _ = _rm(state, 2)   # < detach_init so the fresh head persists
    assert int(state.seq_len) > 0
    for _ in range(TINY.chop_patience + 1):
        state = _add(state, np.array([], np.float32))
    assert int(state.stats.n_chophead) >= 1
    assert int(state.seq_len) == 0
    # nothing lost
    state, res = _rm(state, 16)
    got = np.asarray(res.rm_keys)[np.asarray(res.rm_served)]
    np.testing.assert_allclose(np.sort(got), np.arange(2, 18))


def test_capacity_drop_accounting():
    """Past capacity the queue drops the LARGEST keys and counts them."""
    state = init(TINY)
    total = TINY.par_cap + 10
    keys = np.arange(total, dtype=np.float32)
    for i in range(0, total, TINY.a_max):
        state = _add(state, keys[i:i + TINY.a_max])
    dropped = int(state.stats.n_dropped)
    assert dropped == 10
    assert _size(state) == TINY.par_cap
    # the smallest keys survive
    state, res = _rm(state, 16)
    got = np.asarray(res.rm_keys)[np.asarray(res.rm_served)]
    np.testing.assert_allclose(np.sort(got), keys[:16])


def _add(state, keys):
    ak = np.full((TINY.a_max,), np.inf, np.float32)
    av = np.zeros((TINY.a_max,), np.int32)
    mask = np.zeros((TINY.a_max,), bool)
    ak[:len(keys)] = keys
    mask[:len(keys)] = True
    state, _ = tick(TINY, state, jnp.asarray(ak), jnp.asarray(av),
                    jnp.asarray(mask), jnp.asarray(0))
    return state


def _rm(state, n):
    ak = jnp.full((TINY.a_max,), jnp.inf, jnp.float32)
    av = jnp.zeros((TINY.a_max,), jnp.int32)
    mask = jnp.zeros((TINY.a_max,), bool)
    return tick(TINY, state, ak, av, mask, jnp.asarray(n))


def test_elimination_stats_balanced_mix():
    """Balanced 50/50 mixes should eliminate the majority of operations
    (paper Figs. 7–8: 'for balanced workloads most operations eliminate')."""
    cfg = CFG
    state = init(cfg)
    rng = np.random.default_rng(0)
    # warm the queue (paper: 2000 elements before measuring)
    for i in range(4):
        ak = np.full((cfg.a_max,), np.inf, np.float32)
        av = np.zeros((cfg.a_max,), np.int32)
        mask = np.zeros((cfg.a_max,), bool)
        k = rng.uniform(0, 1000, cfg.a_max).astype(np.float32)
        ak[:] = k
        mask[:] = True
        state, _ = tick(cfg, state, jnp.asarray(ak), jnp.asarray(av),
                        jnp.asarray(mask), jnp.asarray(0))
    # tick() donates its state argument: snapshot the counters as host
    # ints, a live reference would die with the donated buffers
    base = jax.tree.map(int, state.stats)
    for t in range(50):
        n = cfg.a_max // 2
        ak = np.full((cfg.a_max,), np.inf, np.float32)
        av = np.zeros((cfg.a_max,), np.int32)
        mask = np.zeros((cfg.a_max,), bool)
        ak[:n] = rng.uniform(0, 1000, n)
        mask[:n] = True
        state, _ = tick(cfg, state, jnp.asarray(ak), jnp.asarray(av),
                        jnp.asarray(mask), jnp.asarray(n))
    s = state.stats
    eliminated = int(s.add_imm_elim - base.add_imm_elim
                     + s.add_upc_elim - base.add_upc_elim)
    total_adds = 50 * (cfg.a_max // 2)
    assert eliminated / total_adds > 0.5, (
        f"only {eliminated}/{total_adds} adds eliminated on balanced mix")


@pytest.mark.parametrize("start", [0, 7, 24, 30, 45])
@pytest.mark.parametrize("dtype,fill", [(jnp.float32, np.inf),
                                        (jnp.int32, EMPTY_VAL)])
def test_take_window_scalar_start_matches_batched(start, dtype, fill):
    """The scalar-start window (a dynamic slice) and the batched-start one
    (a gather) cut the same window, fill past the end included."""
    arr = jnp.arange(2, 62, 2).astype(dtype)           # 30 slots
    rows = jnp.stack([arr, arr + 1])
    exp = np.full(12, fill, np.dtype(dtype))
    inside = np.asarray(arr)[start:start + 12]
    exp[:len(inside)] = inside
    scalar = _take_window(arr, start, 12, fill)
    batched = _take_window(arr[None], jnp.asarray([start]), 12, fill)[0]
    assert scalar.dtype == batched.dtype == arr.dtype
    np.testing.assert_array_equal(np.asarray(scalar), exp)
    np.testing.assert_array_equal(np.asarray(batched), exp)
    # lane-major rows under one scalar start cut every row alike
    np.testing.assert_array_equal(
        np.asarray(_take_window(rows, start, 12, fill)),
        np.asarray(_take_window(rows, jnp.asarray([start, start]), 12,
                                fill)))
