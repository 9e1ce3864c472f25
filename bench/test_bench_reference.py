"""The exact reference flags each kind of wrong answer, and only those."""

import numpy as np
import pytest

from bench.reference import Reference


def _loaded(keys):
    ref = Reference(id_bound=1000)
    ids = np.arange(len(keys))
    ref.add(np.asarray(keys, np.float32), ids)
    return ref


def _exact_serve(keys, ids, r):
    order = np.lexsort((ids, keys))[:r]
    return keys[order], ids[order]


def _brute_rank_errors(union, served):
    """Rank errors as RankErrorMeter defines them, one key at a time."""
    pool = sorted(union)
    out = []
    for i, k in enumerate(sorted(served)):
        pos = pool.index(k) + sum(1 for s in sorted(served)[:i] if s == k)
        out.append(pos - i)
    return out


def test_exact_stream_passes_with_ties():
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 20, 60).astype(np.float32)     # many ties
    ref = _loaded(keys)
    res_k, res_i = keys.copy(), np.arange(60)
    for t in range(5):
        ak = rng.integers(0, 20, 8).astype(np.float32)
        ai = np.arange(60 + 8 * t, 68 + 8 * t)
        res_k, res_i = np.concatenate([res_k, ak]), np.concatenate([res_i, ai])
        sk, si = _exact_serve(res_k, res_i, 6)
        # among tied keys at the boundary any id may be served
        si = si[::-1].copy()
        sk = res_k[np.searchsorted(res_i, si)]
        err = ref.tick(ak, ai, 6, sk, si)
        assert err.size == 6 and (err == 0).all()
        keep = ~np.isin(res_i, si)
        res_k, res_i = res_k[keep], res_i[keep]
    assert ref.bad_pairs == ref.count_gap == 0
    assert ref.resident_gap(res_k, res_i) == 0
    assert len(ref) == res_k.size


def test_wrong_serve_shows_as_rank_error():
    ref = _loaded(np.arange(10, dtype=np.float32))
    err = ref.tick([], [], 2, np.float32([0, 5]), [0, 5])
    assert err.tolist() == [0, 4]
    assert ref.bad_pairs == 0


def test_rank_error_matches_brute_force():
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 30, 80).astype(np.float32)
    ref = _loaded(keys)
    ak = rng.integers(0, 30, 10).astype(np.float32)
    union = np.concatenate([keys, ak])
    pick = rng.choice(union.size, 12, replace=False)
    ids = np.concatenate([np.arange(80), np.arange(80, 90)])
    err = ref.tick(ak, np.arange(80, 90), 12, union[pick], ids[pick])
    assert sorted(err.tolist()) == sorted(_brute_rank_errors(union, union[pick]))


def test_lost_key_shows_in_the_resident_pairs():
    keys = np.arange(10, dtype=np.float32)
    ref = _loaded(keys)
    ref.tick([], [], 2, keys[:2], [0, 1])
    assert ref.resident_gap(keys[2:], np.arange(2, 10)) == 0
    assert ref.resident_gap(keys[3:], np.arange(3, 10)) == 1     # one lost
    dup = np.concatenate([keys[2:], keys[4:5]])
    assert ref.resident_gap(dup, np.append(np.arange(2, 10), 4)) == 1


def test_swapped_payload_is_a_bad_pair():
    ref = _loaded(np.arange(10, dtype=np.float32))
    ref.tick([], [], 2, np.float32([0, 1]), [1, 0])
    assert ref.bad_pairs == 2


def test_served_twice_and_invented_are_bad_pairs():
    ref = _loaded(np.arange(10, dtype=np.float32))
    ref.tick([], [], 2, np.float32([0, 1]), [0, 1])
    ref.tick([], [], 2, np.float32([0, 2]), [0, 2])       # id 0 again
    assert ref.bad_pairs == 1
    ref.tick([], [], 1, np.float32([3.5]), [3])           # altered key
    assert ref.bad_pairs == 2


def test_keys_rounded_to_bfloat16_are_bad_pairs():
    rng = np.random.default_rng(2)
    keys = rng.uniform(0, 1e5, 200).astype(np.float32)
    ref = _loaded(keys)
    sk, si = _exact_serve(keys, np.arange(200), 50)
    bits = sk.view(np.uint32)
    rounded = ((bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000).view(np.float32)
    ref.tick([], [], 50, rounded, si)
    assert ref.bad_pairs >= 45


def test_short_and_long_serves_are_count_gaps():
    ref = _loaded(np.arange(10, dtype=np.float32))
    ref.tick([], [], 3, np.float32([0, 1]), [0, 1])
    assert ref.count_gap == 1
    ref.tick([], [], 1, np.float32([2, 3]), [2, 3])
    assert ref.count_gap == 2
    # asking more than is resident: only what is there is due
    ref.tick([], [], 100, np.arange(4, 10, dtype=np.float32), np.arange(4, 10))
    assert ref.count_gap == 2


def test_adds_must_carry_fresh_ids():
    ref = _loaded(np.arange(4, dtype=np.float32))
    with pytest.raises(ValueError, match="not unique"):
        ref.add(np.float32([9]), [2])
    with pytest.raises(ValueError, match="not unique"):
        ref.add(np.float32([9, 8]), [5, 5])
