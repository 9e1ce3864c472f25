"""The traffic generator and the payload id pool."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import generate

MIXES = Path(__file__).resolve().parent / "traffic"


def _mix(name):
    with open(MIXES / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("mix", ["hold", "uniform"])
def test_same_seed_same_traffic(mix):
    a = generate.Traffic(_mix(mix), width=64, resident=500, seed=2**31 + 9)
    b = generate.Traffic(_mix(mix), width=64, resident=500, seed=2**31 + 9)
    c = generate.Traffic(_mix(mix), width=64, resident=500, seed=2**31 + 10)
    np.testing.assert_array_equal(a.load_keys(), b.load_keys())
    # a block does not depend on which blocks were made before it
    np.testing.assert_array_equal(a.block(3).base, b.block(3).base)
    np.testing.assert_array_equal(a.block(0).base, b.block(0).base)
    np.testing.assert_array_equal(a.keys(a.block(0), 5, 123.0),
                                  b.keys(b.block(0), 5, 123.0))
    assert not np.array_equal(a.load_keys(), c.load_keys())
    assert not np.array_equal(a.block(0).base, c.block(0).base)


@pytest.mark.parametrize("mix", ["hold", "uniform"])
def test_every_tick_asks_the_same_work(mix):
    t = generate.Traffic(_mix(mix), width=64, resident=500, seed=1)
    assert (t.n_add, t.n_rm) == (32, 32)
    blk = t.block(1)
    assert blk.base.shape == (generate.BLOCK, 32)
    for i in range(generate.BLOCK):
        keys = t.keys(blk, i, 50.0)
        assert keys.shape == (32,) and keys.dtype == np.float32
        assert np.isfinite(keys).all()
    keys = t.load_keys()
    assert keys.shape == (500,) and keys.min() >= 0 and keys.max() < 1e5


def test_hold_adds_land_just_above_the_clock():
    t = generate.Traffic(_mix("hold"), width=64, resident=500, seed=4)
    assert t.closed_loop and t.clock0 == 0.0
    step = 1e5 / 500
    blk = t.block(2)
    inc = np.stack([t.keys(blk, i, 5000.0) for i in range(generate.BLOCK)])
    inc = inc.astype(np.float64) - 5000.0
    assert (inc >= -1e-2).all()
    assert abs(inc.mean() / (8 * step) - 1) < 0.1
    # the same tick at a later clock: the same increments, moved with it
    np.testing.assert_allclose(t.keys(blk, 7, 9000.0).astype(np.float64),
                               inc[7] + 9000.0, atol=1e-2)


def test_without_jitter_every_seed_draws_the_same_multisets():
    """The hold mix: the seed changes the order of the keys, not the
    keys, so it cannot change the queue's work."""
    a = generate.Traffic(_mix("hold"), width=64, resident=500, seed=1)
    b = generate.Traffic(_mix("hold"), width=64, resident=500, seed=2**35)
    ka, kb = a.load_keys(), b.load_keys()
    assert not np.array_equal(ka, kb)
    np.testing.assert_array_equal(np.sort(ka), np.sort(kb))
    np.testing.assert_allclose(np.sort(ka), (np.arange(500) + 0.5) * 200,
                               rtol=1e-6)
    for blk in (0, 3):
        ba, bb = a.block(blk).base, b.block(blk).base
        assert not np.array_equal(ba, bb)
        np.testing.assert_array_equal(np.sort(ba, axis=1), np.sort(bb, axis=1))
        # every tick the same increments
        np.testing.assert_array_equal(np.sort(ba, axis=1),
                                      np.sort(ba[:1], axis=1).repeat(64, 0))


def test_uniform_keys_do_not_follow_the_clock():
    t = generate.Traffic(_mix("uniform"), width=64, resident=500, seed=4)
    assert not t.closed_loop and t.block(0).sign is None
    blk = t.block(0)
    np.testing.assert_array_equal(t.keys(blk, 3, 0.0), t.keys(blk, 3, 7e4))
    np.testing.assert_array_equal(t.keys(blk, 3, 0.0),
                                  blk.base[3].astype(np.float32))


def test_mix_of_parts_keeps_each_share_every_tick():
    mix = _mix("uniform")
    mix["add"] = [
        {"share": 0.75, "dist": "uniform", "lo": 0.0, "hi": 100000.0},
        {"share": 0.25, "dist": "urgent", "mean_spacings": 4, "quantum": 1},
    ]
    t = generate.Traffic(mix, width=64, resident=500, seed=6)
    assert t.closed_loop and [n for _, n in t.parts] == [24, 8]
    blk = t.block(0)
    clock = 50000.0
    below = [(t.keys(blk, i, clock) < clock).sum()
             for i in range(generate.BLOCK)]
    # the urgent quarter lies below the clock; uniform keys at most a
    # stratum or two of the 24 fall there too
    assert all(8 <= n <= 8 + 13 for n in below)
    urgent = blk.sign == -1
    assert (urgent.sum(axis=1) == 8).all()
    # urgent keys sit on a grid of one key spacing (200): they tie
    off = blk.base[urgent]
    np.testing.assert_array_equal(off, np.floor(off / 200) * 200)
    assert np.unique(off).size < off.size
    # the parts are shuffled together, not in fixed columns
    assert not (urgent[:, -8:]).all()


def test_draws_are_stratified_and_shuffled():
    t = generate.Traffic(_mix("uniform"), width=64, resident=500, seed=8)
    keys = t.load_keys().astype(np.float64)
    # one key in each of the 500 strata of width 200
    np.testing.assert_array_equal(np.sort(np.floor(keys / 200)), np.arange(500))
    assert not np.all(np.diff(keys) > 0)
    blk = t.block(0).base
    for row in blk[:4]:
        np.testing.assert_array_equal(np.sort(np.floor(row / (1e5 / 32))),
                                      np.arange(32))
    # two seeds: nearly the same multiset, in another order
    other = generate.Traffic(_mix("uniform"), width=64, resident=500,
                             seed=9).load_keys().astype(np.float64)
    assert np.abs(np.sort(keys) - np.sort(other)).max() < 200
    assert not np.array_equal(keys, other)


def test_seed_beyond_32_bits():
    a = generate.Traffic(_mix("uniform"), width=8, resident=8, seed=2**40 + 3)
    b = generate.Traffic(_mix("uniform"), width=8, resident=8, seed=2**40 + 4)
    assert not np.array_equal(a.block(0).base, b.block(0).base)
    generate.Traffic(_mix("uniform"), width=8, resident=8, seed=-5).block(0)


def test_id_pool_unique_among_outstanding_and_recycled():
    pool = generate.IdPool(100)
    a = pool.take(60)
    assert np.unique(a).size == 60
    pool.give(a[:30])
    b = pool.take(70)                  # 40 never used, then the 30 given back
    out = np.concatenate([a[30:], b])
    assert np.unique(out).size == out.size == 100
    np.testing.assert_array_equal(np.sort(b[40:]), np.sort(a[:30]))
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.take(1)


def test_id_pool_ignores_ids_not_out():
    pool = generate.IdPool(10)
    a = pool.take(5)
    pool.give([a[0], a[0], 7, -1, 99])     # a duplicate, one never out, junk
    assert len(pool) == 6
    b = pool.take(6)
    assert np.unique(np.concatenate([a[1:], b])).size == 10


def test_bad_mix_is_refused():
    mix = _mix("uniform")
    mix["add"] = {"dist": "zipf"}
    with pytest.raises(ValueError, match="unknown key distribution"):
        generate.Traffic(mix, width=8, resident=8, seed=0)
    mix = _mix("uniform")
    mix["p_add"] = 1.5
    with pytest.raises(ValueError, match="p_add"):
        generate.Traffic(mix, width=8, resident=8, seed=0)
    mix = _mix("uniform")
    mix["add"] = [dict(mix["add"], share=0.5), dict(mix["add"], share=0.4)]
    with pytest.raises(ValueError, match="shares"):
        generate.Traffic(mix, width=8, resident=8, seed=0)
