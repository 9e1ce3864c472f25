"""Per-kernel validation: Pallas (interpret=True) vs the pure-jnp oracles,
swept across shapes and dtypes, plus the pallas-backed tick equivalence.

Off-TPU the kernels run only in interpret mode, asked for by name
("pallas_interpret", or interpret=True at direct kernel calls): Mosaic
refuses them for v5e (tests/test_tpu_compile.py).
"""

import zlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops
from repro.kernels import ref
from repro.kernels.bitonic import bitonic_sort_kvf
from repro.kernels.merge_consume import merge_sorted_kvf
from repro.kernels.radix_select import radix_select_threshold

# resolved ONCE, config-style — per-call backend strings are deprecated
_PALLAS = ops.resolve_backend("pallas_interpret")
_JNP = ops.resolve_backend("jnp")


# ---------------------------------------------------------------------------
# bitonic co-sort
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,n", [(1, 8), (4, 64), (2, 256), (1, 1024)])
@pytest.mark.parametrize("key_dist", ["uniform", "dups", "inf_pad",
                                      "negative"])
def test_bitonic_shapes(rows, n, key_dist):
    rng = np.random.default_rng(hash((rows, n, key_dist)) % 2 ** 31)
    k = rng.uniform(-50, 50, (rows, n)).astype(np.float32)
    if key_dist == "dups":
        k[:, : n // 2] = 7.0
    if key_dist == "inf_pad":
        k[rng.random((rows, n)) < 0.3] = np.inf
    if key_dist == "negative":
        k = -np.abs(k)
    v = rng.integers(0, 1 << 20, (rows, n)).astype(np.int32)
    f = rng.integers(0, 2, (rows, n)).astype(np.int32)
    ok, ov, of = bitonic_sort_kvf(jnp.asarray(k), jnp.asarray(v),
                                  jnp.asarray(f), interpret=True)
    rk, rv, rf = ref.ref_sort_kvf(jnp.asarray(k), jnp.asarray(v),
                                  jnp.asarray(f))
    np.testing.assert_array_equal(np.asarray(ok), np.asarray(rk))
    for r in range(rows):  # payload multiset per row (network unstable)
        assert sorted(zip(k[r], v[r])) == sorted(
            zip(np.asarray(ok)[r], np.asarray(ov)[r]))


def test_bitonic_rejects_non_pow2():
    with pytest.raises(ValueError):
        bitonic_sort_kvf(jnp.zeros((1, 12)), jnp.zeros((1, 12), jnp.int32),
                         jnp.zeros((1, 12), jnp.int32), interpret=True)


# ---------------------------------------------------------------------------
# rank-merge via one-hot MXU scatter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m,tile", [(256, 256, 256), (768, 256, 128),
                                      (96, 32, 32), (1024, 512, 256)])
def test_merge_shapes(n, m, tile):
    rng = np.random.default_rng(n * 1000 + m)
    na, nb = rng.integers(0, n + 1), rng.integers(0, m + 1)
    a = np.full(n, np.inf, np.float32)
    b = np.full(m, np.inf, np.float32)
    a[:na] = np.sort(rng.uniform(-10, 50, na)).astype(np.float32)
    b[:nb] = np.sort(rng.uniform(-10, 50, nb)).astype(np.float32)
    if na > 4 and nb > 4:  # cross-stream duplicates
        b[:3] = a[:3]
        b = np.sort(b)
    av = rng.integers(0, 1 << 20, n).astype(np.int32)
    bv = rng.integers(0, 1 << 20, m).astype(np.int32)
    af = np.zeros(n, np.int32)
    bf = np.ones(m, np.int32)
    got = merge_sorted_kvf(*map(jnp.asarray, (a, av, af, b, bv, bf)),
                           tile=tile, interpret=True)
    exp = ref.ref_merge_sorted(*map(jnp.asarray, (a, av, af, b, bv, bf)))
    for g, e in zip(got, exp):
        np.testing.assert_array_equal(
            np.nan_to_num(np.asarray(g, np.float64), posinf=1e300),
            np.nan_to_num(np.asarray(e, np.float64), posinf=1e300))


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10)
def test_merge_property(seed):
    rng = np.random.default_rng(seed)
    n, m = 128, 64
    na, nb = rng.integers(0, n + 1), rng.integers(0, m + 1)
    a = np.full(n, np.inf, np.float32)
    b = np.full(m, np.inf, np.float32)
    a[:na] = np.sort(rng.integers(0, 30, na)).astype(np.float32)  # dups
    b[:nb] = np.sort(rng.integers(0, 30, nb)).astype(np.float32)
    av = np.arange(n, dtype=np.int32)
    bv = np.arange(m, dtype=np.int32) + 1000
    z = np.zeros_like(av)[:n]
    got_k, got_v, _ = merge_sorted_kvf(
        jnp.asarray(a), jnp.asarray(av), jnp.asarray(z),
        jnp.asarray(b), jnp.asarray(bv), jnp.asarray(np.zeros(m, np.int32)),
        tile=64, interpret=True)
    # merged keys sorted; payload multiset conserved
    gk = np.asarray(got_k)
    fin = gk[np.isfinite(gk)]
    assert np.all(np.diff(fin) >= 0)
    assert sorted(np.asarray(got_v).tolist()) == sorted(
        av.tolist() + bv.tolist())


# ---------------------------------------------------------------------------
# gather-free rank merge: the jnp path of merge_sorted
# ---------------------------------------------------------------------------

def _sorted_rows(rng, lead, width, n_fin, pool):
    """[*lead, width] rows ascending: n_fin keys drawn from `pool`, then
    an INF tail."""
    k = np.full(lead + (width,), np.inf, np.float32)
    k[..., :n_fin] = np.sort(rng.choice(pool, lead + (n_fin,)), axis=-1)
    return k


def _merge_case(case):
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    pool = np.array([0.0, 1.0, 1.5, 2.0, 7.25], np.float32)
    wide = rng.uniform(-40, 40, 64).astype(np.float32)
    lead, n, m, na, nb = (), 96, 40, 96, 40
    if case == "inf_tails":
        n, m, na, nb, pool = 128, 64, 70, 23, wide
    elif case == "all_inf_a":
        na = 0
    elif case == "all_inf_b":
        nb = 0
    elif case == "n1":
        n, m, na, nb = 1, 33, 1, 20
    elif case == "m1":
        n, m, na, nb = 50, 1, 41, 1
    elif case == "m_gt_n":
        n, m, na, nb = 17, 200, 17, 150
    elif case == "lane_major":
        lead, n, m, na, nb = (3,), 64, 24, 50, 24
    a = _sorted_rows(rng, lead, n, na, pool)
    b = _sorted_rows(rng, lead, m, nb, pool)
    lo = (1 << 24) - 512 if case == "payload_2_24" else 0
    av = rng.integers(lo, 1 << 24, lead + (n,)).astype(np.int32)
    bv = rng.integers(lo, 1 << 24, lead + (m,)).astype(np.int32)
    af = rng.integers(lo, 1 << 24, lead + (n,)).astype(np.int32)
    bf = rng.integers(lo, 1 << 24, lead + (m,)).astype(np.int32)
    return a, av, af, b, bv, bf


def _check_merge_shift(a, av, af, b, bv, bf):
    """_merge_sorted_shift against a stable argsort of the concatenation
    (ties a-first) and, row by row, against ref.ref_merge_sorted."""
    got = [np.asarray(x) for x in ops._merge_sorted_shift(
        *map(jnp.asarray, (a, av, af, b, bv, bf)))]
    order = np.argsort(np.concatenate([a, b], -1), axis=-1, kind="stable")
    for g, x, y in zip(got, (a, av, af), (b, bv, bf)):
        np.testing.assert_array_equal(
            g, np.take_along_axis(np.concatenate([x, y], -1), order, -1))
    n, m = a.shape[-1], b.shape[-1]
    rows = [x.reshape(-1, x.shape[-1]) for x in (a, av, af, b, bv, bf)]
    for r in range(rows[0].shape[0]):
        exp = ref.ref_merge_sorted(*(jnp.asarray(x[r]) for x in rows))
        for g, e in zip(got, exp):
            np.testing.assert_array_equal(g.reshape(-1, n + m)[r],
                                          np.asarray(e))


@pytest.mark.parametrize("case", [
    "ties", "inf_tails", "all_inf_a", "all_inf_b", "n1", "m1", "m_gt_n",
    "lane_major", "payload_2_24"])
def test_merge_sorted_shift_matches_reference(case):
    _check_merge_shift(*_merge_case(case))


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10)
def test_merge_shift_property(seed):
    rng = np.random.default_rng(seed)
    # a few fixed shapes: each one compiles once
    lead, n, m = [((), 1, 7), ((), 33, 5), ((2,), 64, 64),
                  ((), 20, 100)][seed % 4]
    a = _sorted_rows(rng, lead, n, int(rng.integers(0, n + 1)),
                     np.arange(6, dtype=np.float32))
    b = _sorted_rows(rng, lead, m, int(rng.integers(0, m + 1)),
                     np.arange(6, dtype=np.float32))
    av = rng.integers(0, 1 << 24, lead + (n,)).astype(np.int32)
    bv = rng.integers(0, 1 << 24, lead + (m,)).astype(np.int32)
    _check_merge_shift(a, av, np.zeros_like(av), b, bv, np.ones_like(bv))


def _primitive_names(jaxpr):
    """Every primitive in a jaxpr, nested jaxprs (jit, cond) included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _primitive_names(sub)


def test_merge_sorted_jnp_has_no_gather():
    """merge_sorted's jnp backend is the gather-free merge (no gather, no
    searchsorted loop) and agrees with the co-rank merge the repairs
    keep."""
    args = tuple(map(jnp.asarray, _merge_case("ties")))
    merge = lambda *x: ops.merge_sorted(*x, backend=_JNP)   # noqa: E731
    names = set(_primitive_names(jax.make_jaxpr(merge)(*args).jaxpr))
    assert {"gather", "while", "scatter"}.isdisjoint(names), names
    corank = set(_primitive_names(
        jax.make_jaxpr(ops._merge_sorted_corank)(*args).jaxpr))
    assert "gather" in corank                   # the walk sees a gather
    for g, c in zip(merge(*args), ops._merge_sorted_corank(*args)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(c))


# ---------------------------------------------------------------------------
# radix threshold select
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("length", [32, 256, 4096])
def test_radix_threshold(length):
    rng = np.random.default_rng(length)
    for trial in range(3):
        nfin = int(rng.integers(1, length + 1))
        keys = np.full(length, np.inf, np.float32)
        keys[:nfin] = rng.uniform(-100, 100, nfin).astype(np.float32)
        if nfin > 8:
            keys[2:6] = keys[1]   # duplicates around the threshold
        rng.shuffle(keys)
        for k in [0, 1, nfin // 2, nfin]:
            tau, nb = radix_select_threshold(jnp.asarray(keys), k,
                                             interpret=True)
            rtau, rnb = ref.ref_select_threshold(jnp.asarray(keys), k)
            assert float(tau) == float(rtau), (length, k)
            assert int(nb) == int(rnb), (length, k)


def test_select_k_smallest_composite():
    """radix select + compaction + bitonic == oracle k-smallest."""
    rng = np.random.default_rng(0)
    length, k_max = 512, 64
    keys = rng.uniform(0, 1000, length).astype(np.float32)
    vals = np.arange(length, dtype=np.int32)
    for k in [0, 1, 17, 64]:
        gk, gv = ops.select_k_smallest(jnp.asarray(keys), jnp.asarray(vals),
                                       k, k_max, backend=_PALLAS)
        ek, ev = ref.ref_select_k(jnp.asarray(keys), jnp.asarray(vals), k,
                                  k_max)
        np.testing.assert_array_equal(
            np.nan_to_num(np.asarray(gk), posinf=1e30),
            np.nan_to_num(np.asarray(ek), posinf=1e30))
        np.testing.assert_array_equal(np.asarray(gv), np.asarray(ev))


@pytest.mark.parametrize("length", [64, 1024])
def test_radix_threshold_edges(length):
    """Pinned edge guarantees (see radix_select_threshold docstring):
    k=0, all-INF streams, negative keys, k past the finite count."""
    # k = 0 -> sentinel (-inf, 0) regardless of content
    keys = jnp.asarray(np.random.default_rng(0).uniform(
        -5, 5, length), jnp.float32)
    tau, nb = radix_select_threshold(keys, 0, interpret=True)
    assert float(tau) == -np.inf and int(nb) == 0

    # all-INF stream: any k > 0 hits the INF ceiling
    inf_keys = jnp.full((length,), jnp.inf, jnp.float32)
    for k in (1, length // 2, length):
        tau, nb = radix_select_threshold(inf_keys, k, interpret=True)
        assert float(tau) == np.inf and int(nb) == 0

    # negative keys (the float->uint32 monotone map's sign branch)
    neg = np.sort(-np.abs(np.random.default_rng(1).uniform(
        0.5, 100, length))).astype(np.float32)
    shuffled = neg.copy()
    np.random.default_rng(2).shuffle(shuffled)
    for k in (1, 7, length):
        tau, nb = radix_select_threshold(jnp.asarray(shuffled), k,
                                         interpret=True)
        assert float(tau) == neg[k - 1]
        assert int(nb) == int((neg < neg[k - 1]).sum())

    # k beyond the finite count: tau=INF, n_below = #finite
    half = np.full(length, np.inf, np.float32)
    half[: length // 2] = np.random.default_rng(3).uniform(
        0, 10, length // 2)
    tau, nb = radix_select_threshold(jnp.asarray(half), length,
                                     interpret=True)
    assert float(tau) == np.inf and int(nb) == length // 2


def test_radix_threshold_accepts_bucket_rows():
    rng = np.random.default_rng(5)
    k2 = rng.uniform(0, 100, (8, 32)).astype(np.float32)
    tau2, nb2 = radix_select_threshold(jnp.asarray(k2), 17, interpret=True)
    tau1, nb1 = radix_select_threshold(jnp.asarray(k2.reshape(-1)), 17,
                                       interpret=True)
    assert float(tau2) == float(tau1) and int(nb2) == int(nb1)


def test_select_k_smallest_tie_split():
    """Ties at the threshold resolve by eq_rank: exactly k selected, and
    the tied survivors are the earliest occurrences in stream order."""
    keys = np.array([5.0, 3.0, 5.0, 1.0, 5.0, 5.0, 2.0, 5.0],
                    np.float32)
    vals = np.arange(8, dtype=np.int32)
    # k=5: 1, 2, 3 below tau=5; exactly TWO of the five 5.0s join
    gk, gv = ops.select_k_smallest(jnp.asarray(keys), jnp.asarray(vals),
                                   5, 8, backend=_PALLAS)
    np.testing.assert_array_equal(
        np.asarray(gk)[:5], [1.0, 2.0, 3.0, 5.0, 5.0])
    assert np.isinf(np.asarray(gk)[5:]).all()
    # earliest 5.0s in stream order hold vals {0, 2}
    assert set(np.asarray(gv)[3:5].tolist()) == {0, 2}


def test_merge_sorted_rejects_odd_total():
    """Odd n+m used to ZeroDivisionError in the tile shrink loop."""
    a = jnp.sort(jnp.asarray(np.random.default_rng(0).uniform(
        0, 10, 7), jnp.float32))
    b = jnp.sort(jnp.asarray(np.random.default_rng(1).uniform(
        0, 10, 4), jnp.float32))
    za, zb = jnp.zeros(7, jnp.int32), jnp.zeros(4, jnp.int32)
    with pytest.raises(ValueError, match="even total"):
        ops.merge_sorted(a, za, za, b, zb, zb, backend=_PALLAS)
    # jnp backend has no tiling constraint
    ok, _, _ = ops.merge_sorted(a, za, za, b, zb, zb, backend=_JNP)
    assert ok.shape == (11,)


def test_merge_sorted_rejects_oversized_payloads():
    """|val| >= 2**24 would lose bits in the f32 one-hot matmul."""
    n = 8
    a = jnp.asarray(np.arange(n), jnp.float32)
    b = jnp.asarray(np.arange(n) + 0.5, jnp.float32)
    big = jnp.full((n,), 1 << 24, jnp.int32)
    z = jnp.zeros(n, jnp.int32)
    with pytest.raises(ValueError, match="2\\*\\*24"):
        ops.merge_sorted(a, big, z, b, z, z, backend=_PALLAS)
    # in-bounds payloads pass
    ok_v = jnp.full((n,), (1 << 24) - 1, jnp.int32)
    ops.merge_sorted(a, ok_v, z, b, z, z, backend=_PALLAS)


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"],
                         ids=["jnp", "pallas"])
def test_extract_k_bucketed(backend):
    """Extraction == oracle k-smallest; survivors conserve the multiset
    and keep the range partition."""
    backend = ops.resolve_backend(backend)
    rng = np.random.default_rng(11)
    nb, bc, k_max = 8, 16, 32
    splitters = np.full(nb, np.inf, np.float32)
    edges = np.sort(rng.uniform(0, 100, nb - 1))
    splitters[0] = -np.inf
    splitters[1:] = edges
    keys = np.full((nb, bc), np.inf, np.float32)
    vals = np.full((nb, bc), -1, np.int32)
    counts = rng.integers(0, bc + 1, nb).astype(np.int32)
    nv = 0
    lo = np.concatenate([[0.0], edges])
    hi = np.concatenate([edges, [100.0]])
    for r in range(nb):
        keys[r, :counts[r]] = rng.uniform(lo[r], hi[r], counts[r])
        vals[r, :counts[r]] = np.arange(nv, nv + counts[r])
        nv += counts[r]
    total = int(counts.sum())
    for k in (0, 1, total // 2, min(total, k_max)):
        keff = min(k, total, k_max)   # extraction clamps to store + k_max
        out_k, out_v, nk, nvv, ncnt = ops.extract_k_bucketed(
            jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(counts), k,
            k_max, splitters=jnp.asarray(splitters), backend=backend)
        ek, ev = ref.ref_extract_k_bucketed(
            jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(counts), k,
            k_max)
        np.testing.assert_array_equal(
            np.nan_to_num(np.asarray(out_k), posinf=1e30),
            np.nan_to_num(np.asarray(ek), posinf=1e30))
        np.testing.assert_array_equal(np.asarray(out_v), np.asarray(ev))
        # survivors: counts drop by keff, multiset conserved, ranges kept
        ncnt = np.asarray(ncnt)
        assert ncnt.sum() == total - keff
        surv = []
        nk = np.asarray(nk)
        nvv = np.asarray(nvv)
        for r in range(nb):
            row = list(zip(nk[r, :ncnt[r]], nvv[r, :ncnt[r]]))
            assert all(splitters[r] <= kk for kk, _ in row)
            surv += row
        everything = sorted(
            zip(np.asarray(out_k)[:keff].tolist(),
                np.asarray(out_v)[:keff].tolist())) + sorted(surv)
        expected = []
        for r in range(nb):
            expected += zip(keys[r, :counts[r]].tolist(),
                            vals[r, :counts[r]].tolist())
        assert sorted(everything) == sorted(expected)


# ---------------------------------------------------------------------------
# pallas-backed tick == jnp tick (the integrated hot path)
# ---------------------------------------------------------------------------

def test_tick_pallas_backend_matches_oracle():
    import dataclasses
    from repro.core import EMPTY_VAL, PQConfig, RefPQ, init, tick
    cfg = PQConfig(a_max=32, r_max=32, seq_cap=224, n_buckets=8,
                   bucket_cap=32, detach_min=4, detach_max=64,
                   detach_init=8, backend="pallas_interpret")
    state = init(cfg)
    ref_pq = RefPQ()
    rng = np.random.default_rng(7)
    nv = 0
    for t in range(25):
        n_add = int(rng.integers(0, cfg.a_max + 1))
        n_add = min(n_add, cfg.par_cap - len(ref_pq))
        n_rm = int(rng.integers(0, cfg.r_max + 1))
        keys = rng.uniform(0, 500, n_add).astype(np.float32)
        ak = np.full((cfg.a_max,), np.inf, np.float32)
        av = np.full((cfg.a_max,), EMPTY_VAL, np.int32)
        mask = np.zeros((cfg.a_max,), bool)
        ak[:n_add] = keys
        av[:n_add] = np.arange(nv, nv + n_add)
        mask[:n_add] = True
        nv += n_add
        state, res = tick(cfg, state, jnp.asarray(ak), jnp.asarray(av),
                          jnp.asarray(mask), jnp.asarray(n_rm))
        got = np.sort(np.asarray(res.rm_keys)[np.asarray(res.rm_served)])
        exp = np.sort(np.array(
            [k for k, _ in ref_pq.tick(keys.tolist(), range(n_add), n_rm)
             if k != np.inf], np.float32))
        np.testing.assert_allclose(got, exp)


# ---------------------------------------------------------------------------
# batched search / sort helpers behind the lane-major hot paths
# ---------------------------------------------------------------------------

def test_searchsorted_last_matches_numpy():
    """Exactness across sides, ties, INF padding, int dtypes, and leading
    dims — both the compare-all and the scan lowering."""
    rng = np.random.default_rng(12)
    for trial in range(60):
        n = int(rng.integers(1, 400))
        m = int(rng.integers(1, 300))
        lead = () if trial % 3 == 0 else (int(rng.integers(1, 5)),)
        if trial % 4 == 0:
            a = np.sort(rng.integers(0, 25, lead + (n,)).astype(np.int32),
                        axis=-1)
            v = rng.integers(-3, 30, lead + (m,)).astype(np.int32)
        else:
            pool = np.array([0.0, 0.5, 1.5, 2.5, np.inf], np.float32)
            a = np.sort(rng.choice(pool, lead + (n,)), axis=-1)
            v = rng.choice(np.append(pool, [-1.0, 3.0]), lead + (m,))
        for side in ("left", "right"):
            got = np.asarray(ops.searchsorted_last(
                jnp.asarray(a), jnp.asarray(v), side=side))
            exp = np.stack([
                np.searchsorted(ar, vr, side=side)
                for ar, vr in zip(a.reshape(-1, n), v.reshape(-1, m))
            ]).reshape(lead + (m,))
            np.testing.assert_array_equal(got, exp)


def test_argsort_f32_last_matches_stable_float_argsort():
    rng = np.random.default_rng(3)
    keys = rng.choice([0.0, 1.5, 2.5, np.inf, -4.0, 1e30],
                      (6, 257)).astype(np.float32)
    got = np.asarray(ops.argsort_f32_last(jnp.asarray(keys)))
    exp = np.argsort(keys, axis=-1, kind="stable")
    np.testing.assert_array_equal(got, exp)


def test_sorted_runs_gather_lane_major_matches_per_lane():
    rng = np.random.default_rng(8)
    L, nb, bc = 3, 4, 8
    keys = np.full((L, nb, bc), np.inf, np.float32)
    vals = np.full((L, nb, bc), -1, np.int32)
    counts = rng.integers(0, bc + 1, (L, nb)).astype(np.int32)
    for lane in range(L):
        base = 0.0
        for b in range(nb):
            c = counts[lane, b]
            keys[lane, b, :c] = np.sort(
                rng.uniform(base, base + 10, c)).astype(np.float32)
            vals[lane, b, :c] = rng.integers(0, 99, c)
            base += 10.0
    outs = ops.sorted_runs_gather(jnp.asarray(keys), jnp.asarray(vals),
                                  jnp.asarray(counts), 16)
    for lane in range(L):
        one = ops.sorted_runs_gather(jnp.asarray(keys[lane]),
                                     jnp.asarray(vals[lane]),
                                     jnp.asarray(counts[lane]), 16)
        for batched, single in zip(outs, one):
            np.testing.assert_array_equal(np.asarray(batched)[lane],
                                          np.asarray(single))
