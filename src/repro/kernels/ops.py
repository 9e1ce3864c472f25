"""Public jit'd wrappers over the Pallas kernels, with jnp fallbacks.

Backend selection is a RESOLVED config value, not a per-call string: the
supported path is ``PQConfig(backend=...)`` / ``EngineSpec(backend=...)``
(``repro.core``), which call :func:`resolve_backend` ONCE at config
construction and thread the frozen :class:`KernelBackend` through every
op.  Resolving eagerly makes the backend part of the compiled program's
cache key instead of ambient global state.  Spellings accepted by
:func:`resolve_backend`:

* ``"jnp"`` — pure-jnp path: the oracle, and the path that runs on the
  chip.  Never touches the JAX runtime at resolve time, so configs built
  at import time stay XLA-flag-safe.
* ``"pallas"`` — pl.pallas_call kernels compiled by Mosaic; only valid
  on a TPU (raises elsewhere).
* ``"pallas_interpret"`` — the same kernels with interpret=True, on any
  platform (the off-TPU equivalence tests and the CI interpret leg).
* ``"auto"`` — ``"jnp"`` on every platform; the ``PQ_BACKEND`` env var
  overrides what "auto" resolves to (the CI pallas-interpret leg forces
  it).

Why "auto" never picks Pallas: no Pallas kernel here compiles for a TPU
v5e.  Mosaic refuses the lane-tick megakernel (unaligned ``(1, n)``
lane blocks at L>1; a non-2D ``take_along_axis`` gather at L=1), the
bitonic sort (block alignment), the merge (operand layout) and the radix
select (scalar stores to VMEM).  tests/test_tpu_compile.py pins each
refusal as a strict xfail and compiles the jnp tick at deployment size
beside them, so the PR that makes a kernel compile flips its case.

The per-call ``backend=`` string kwargs on the ops below are DEPRECATED
aliases (they warn and re-resolve per call); in-repo call sites pass the
config's ``KernelBackend`` and a CI grep gate keeps it that way
(tests/test_factory.py::test_no_per_call_backend_strings).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import warnings

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bitonic import bitonic_sort_kvf
from repro.kernels.merge_consume import merge_sorted_kvf
from repro.kernels.radix_select import (_to_sortable_u32,
                                        radix_select_threshold)

INF = jnp.inf
_I32 = jnp.int32

_VAL_EXACT_BOUND = 1 << 24  # payloads ride through f32 matmuls

#: spellings resolve_backend accepts (the config-level vocabulary)
BACKENDS = ("jnp", "pallas", "pallas_interpret", "auto")


@dataclasses.dataclass(frozen=True)
class KernelBackend:
    """Resolved kernel-dispatch choice — frozen and hashable, so it rides
    inside ``PQConfig`` as a static jit argument and the backend is part
    of every compiled program's cache key.

    ``kind``: "jnp" (reference path) or "pallas" (kernel path).
    ``interpret``: pallas bodies execute via the interpreter (off-TPU
    validation) instead of Mosaic.  Meaningless for kind="jnp".
    """

    kind: str
    interpret: bool = False

    @property
    def is_pallas(self) -> bool:
        return self.kind == "pallas"


def resolve_backend(backend) -> KernelBackend:
    """Validate + resolve a backend spelling to a :class:`KernelBackend`.

    Called once at config construction (``PQConfig.__post_init__`` /
    ``factory.resolved_base``).  Only "pallas" probes
    ``jax.default_backend()`` — HERE, eagerly, never inside jit tracing —
    and it raises off-TPU: interpret mode is asked for by name
    ("pallas_interpret"), never reached by fallback.  "auto" is "jnp"
    (module docstring: no Pallas kernel compiles for v5e).
    """
    if isinstance(backend, KernelBackend):
        return backend
    if backend is None:
        backend = "auto"
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown kernel backend {backend!r} (have {BACKENDS})")
    if backend == "auto":
        env = os.environ.get("PQ_BACKEND")
        if env:
            if env not in BACKENDS or env == "auto":
                raise ValueError(
                    f"PQ_BACKEND={env!r} must be one of "
                    f"{tuple(b for b in BACKENDS if b != 'auto')}")
            backend = env
        else:
            backend = "jnp"
    if backend == "jnp":
        return KernelBackend("jnp")
    if backend == "pallas_interpret":
        return KernelBackend("pallas", interpret=True)
    platform = jax.default_backend()
    if platform != "tpu":
        raise ValueError(
            f'backend "pallas" compiles with Mosaic and needs a TPU (this '
            f'process runs on {platform!r}); use "pallas_interpret" for the '
            f'interpreter or "jnp"')
    return KernelBackend("pallas")


def _coerce(backend) -> KernelBackend:
    """Per-op backend arg -> KernelBackend.  ``None`` (the default)
    resolves "auto" silently (jnp unless ``PQ_BACKEND`` says otherwise);
    strings are the deprecated per-call alias and warn — the supported
    path is the config-level ``KernelBackend``.
    """
    if isinstance(backend, KernelBackend):
        return backend
    if backend is None:
        return resolve_backend("auto")
    warnings.warn(
        "per-call backend= strings are deprecated; set backend on "
        "PQConfig/EngineSpec (or pass ops.resolve_backend(...)) instead",
        DeprecationWarning, stacklevel=3)
    return resolve_backend(backend)


def _check_val_bound(*val_arrays) -> None:
    """Reject payloads a f32 matmul cannot carry exactly (|v| >= 2**24).

    The one-hot-matmul merge kernel routes int payloads through f32
    contractions, which are exact only below 2**24.  Concrete (non-traced)
    inputs are checked eagerly; traced/abstract values cannot be
    validated without a checkify round-trip, so under jit the caller
    contract stands unchecked (documented in merge_consume.py).
    """
    import numpy as np
    for v in val_arrays:
        try:
            # concrete arrays convert; tracers raise (version-stable,
            # unlike isinstance checks against jax.core.Tracer)
            arr = np.asarray(v)
        except Exception:
            continue
        if arr.size and np.abs(arr).max() >= _VAL_EXACT_BOUND:
            raise ValueError(
                f"payload magnitude {int(np.abs(arr).max())} >= 2**24; "
                "values this large are not exactly representable through "
                "the f32 one-hot matmul path (see merge_consume.py)")


def searchsorted_last(a, v, side: str = "left"):
    """Batched ``searchsorted`` along the last axis.

    ``a``: [..., n] rows sorted ascending; ``v``: [..., m] queries; equal
    (or broadcastable) leading dims.  Returns i32 insertion points in
    [0, n].  Delegates to ``jnp.searchsorted``'s scan method — measured
    fastest on XLA CPU both 1D and batched (a hand-rolled binary-lift
    gather loop ran 10x slower: per-round ``take_along_axis`` gathers do
    not fuse, while the scan method's compare rounds do).  Leading dims
    ride a ``jax.vmap`` of the scan, which lowers to one batched scan —
    NOT one program per lane — so this is safe in lane-major kernels and
    under further ``vmap``.
    """
    n, m = a.shape[-1], v.shape[-1]
    lead = jnp.broadcast_shapes(a.shape[:-1], v.shape[:-1])
    rows = 1
    for d in lead:
        rows *= d
    if rows * n * m <= (1 << 17):
        # compare-all: one broadcast compare + reduce instead of a
        # log2(n)-round sequential scan.  Inside a lax.scan body every
        # while-round is a latency-bound micro-op, so for small n*m one
        # wide op wins by a large margin (and lowers identically under
        # vmap).  Exact: pos = #{a < v} (left) or #{a <= v} (right).
        # The threshold is conservative — visible shapes may carry a
        # hidden vmap batch factor that multiplies the real work.
        return _searchsorted_compare_all(a, v, side=side)
    # larger shapes: the binary-search scan's rounds already do rows*m
    # of work each, so they are throughput- not latency-bound and the
    # m log n total beats any compare-all (a two-level blocked search
    # was also tried and measured ~4x slower at the merge shapes)
    if a.ndim == 1 and v.ndim == 1:
        return jnp.searchsorted(a, v, side=side).astype(_I32)
    af = jnp.broadcast_to(a, lead + (n,)).reshape(-1, n)
    vf = jnp.broadcast_to(v, lead + (m,)).reshape(-1, m)
    out = jax.vmap(
        lambda ar, vr: jnp.searchsorted(ar, vr, side=side))(af, vf)
    return out.reshape(lead + (m,)).astype(_I32)


def _searchsorted_compare_all(a, v, side: str = "left"):
    """Exact batched searchsorted as one broadcast compare + reduce.

    pos = #{a < v} (left) / #{a <= v} (right) — no scan, no gather, no
    scatter, so it lowers inside a Pallas kernel body (the megakernel's
    :func:`kernel_safe_primitives` swaps this in unconditionally; the
    public :func:`searchsorted_last` already picks it for small shapes,
    which is what makes the swap bit-exact)."""
    cmp = (a[..., None, :] < v[..., :, None] if side == "left"
           else a[..., None, :] <= v[..., :, None])
    return jnp.sum(cmp, axis=-1, dtype=_I32)


def argsort_f32_last(keys, *, stable: bool = True):
    """argsort float rows along the last axis via the monotone
    float→uint32 transform (radix_select's map: total order preserved,
    INF sorts last).  XLA CPU's float sort comparator (NaN-aware total
    order) runs ~4x slower than the integer sort; the u32 map is
    bijective, so equal keys are equal u32s and stability carries over.
    Keys must be NaN-free (the PQ uses INF padding, never NaN).  Only
    observable difference: -0.0 orders strictly before 0.0 instead of
    tying — a tie permutation under float comparison, inside the PQ's
    multiset contract for equal keys.
    """
    return jnp.argsort(_to_sortable_u32(keys), axis=-1, stable=stable)


def _argsort_network_stable(keys, *, stable: bool = True):
    """Stable f32 argsort as a bitonic compare/select network — the
    Mosaic-lowerable twin of :func:`argsort_f32_last` (no ``sort_p``
    primitive, which Pallas kernel bodies cannot carry).

    The network sorts (u32 key, index) pairs LEXICOGRAPHICALLY: the
    index payload breaks every key tie, and because indices are a
    permutation the order is total — so the network's output indices are
    exactly the unique stable-argsort permutation, bit-identical to
    ``jnp.argsort(u32, stable=True)`` regardless of how either handles
    ties internally.  Rows pad to a power of two with (0xFFFFFFFF, n+i)
    sentinels: no finite f32 (nor +inf, 0xFF800000) maps that high, and
    the index tiebreak keeps even a hypothetical tie behind every real
    element.  O(n log^2 n) compares — only ever used at the lane tick's
    small widths (a_max / bucket_cap rows)."""
    del stable  # the lexicographic network is always stable
    n = keys.shape[-1]
    lead = keys.shape[:-1]
    if n == 1:
        return jnp.zeros(lead + (1,), _I32)
    np2 = 1 << (n - 1).bit_length()
    full = lead + (np2,)
    ku = _to_sortable_u32(keys)
    ki = jax.lax.broadcasted_iota(_I32, full, len(full) - 1)
    if np2 > n:
        ku = jnp.concatenate(
            [ku, jnp.full(lead + (np2 - n,), jnp.uint32(0xFFFFFFFF),
                          ku.dtype)], axis=-1)
    size = 2
    while size <= np2:
        stride = size // 2
        while stride >= 1:
            g = np2 // (2 * stride)
            ks = ku.reshape(lead + (g, 2, stride))
            vs = ki.reshape(lead + (g, 2, stride))
            ka, kb = ks[..., 0, :], ks[..., 1, :]
            ia, ib = vs[..., 0, :], vs[..., 1, :]
            # each (2, stride) group sits inside one size-block (2*stride
            # divides size), so the merge direction is constant per group
            blk = jax.lax.broadcasted_iota(_I32, (g, stride), 0)
            desc = ((blk * (2 * stride)) // size) % 2 == 1
            gt = (ka > kb) | ((ka == kb) & (ia > ib))
            swap = gt ^ desc
            ku = jnp.stack([jnp.where(swap, kb, ka),
                            jnp.where(swap, ka, kb)], axis=-2).reshape(full)
            ki = jnp.stack([jnp.where(swap, ib, ia),
                            jnp.where(swap, ia, ib)], axis=-2).reshape(full)
            stride //= 2
        size *= 2
    return ki[..., :n]


@contextlib.contextmanager
def kernel_safe_primitives():
    """Swap the two batched search/sort helpers for Pallas-kernel-safe
    equivalents while a kernel body is being traced.

    The lane-tick megakernel (kernels/lane_tick.py) runs the pqueue pass
    chain INSIDE a ``pallas_call`` body; two of the primitives those
    passes reach for do not belong in a kernel: ``jnp.searchsorted``'s
    scan method (a while loop per round) and ``jnp.argsort`` (the
    ``sort_p`` primitive).  Both have exact, gather/scan-free twins —
    compare-all counting and the stable lexicographic bitonic network —
    so swapping is a pure lowering choice, never a semantic one: results
    stay bit-identical (asserted by tests/test_lane_megakernel.py).

    Tracing of a ``pallas_call`` kernel happens eagerly at call time, so
    wrapping the call is sufficient; the swap is restored before any
    non-kernel code runs again."""
    global searchsorted_last, argsort_f32_last
    prev = (searchsorted_last, argsort_f32_last)
    searchsorted_last = _searchsorted_compare_all
    argsort_f32_last = _argsort_network_stable
    try:
        yield
    finally:
        searchsorted_last, argsort_f32_last = prev


def sort_kvf(keys, vals, flags, *, backend=None):
    """Co-sort (keys, vals, flags) by key ascending along the last axis.

    Accepts any leading dims ([n], [rows, n], [lanes, rows, n], ...);
    the pallas path flattens the leading dims onto the bitonic kernel's
    rows grid (lane-major, not vmapped one lane at a time).
    """
    bk = _coerce(backend)
    if not bk.is_pallas:
        order = argsort_f32_last(keys)
        return (jnp.take_along_axis(keys, order, axis=-1),
                jnp.take_along_axis(vals, order, axis=-1),
                jnp.take_along_axis(flags, order, axis=-1))
    lead = keys.shape[:-1]
    n = keys.shape[-1]
    ok, ov, of = bitonic_sort_kvf(keys.reshape(-1, n),
                                  vals.astype(_I32).reshape(-1, n),
                                  flags.astype(_I32).reshape(-1, n),
                                  interpret=bk.interpret)
    return (ok.reshape(lead + (n,)), ov.reshape(lead + (n,)),
            of.reshape(lead + (n,)))


def _merge_sorted_corank(ak, av, af, bk, bv, bf):
    """Gather-only rank merge (ties a-first): the repairs' merge
    (``pqueue.rank_merge_kv``), where n = par_cap makes the
    compare-all counting of :func:`_merge_sorted_shift` n * m pairs.

    Functionally identical to ref.ref_merge_sorted, but assembled with
    searchsorted + gathers instead of position scatters: XLA CPU
    serializes scatters, and even an argsort of the concatenation beats
    them; co-rank gathers beat both (~1.8x over the argsort at 16k+4k).
    Supports any equal leading dims (lane-major merges in the sharded
    tick's repair passes run all lanes through one call).
    """
    n, m = ak.shape[-1], bk.shape[-1]
    lead = ak.shape[:-1]
    pa = (jnp.arange(n, dtype=_I32)
          + searchsorted_last(bk, ak, side="left"))      # [..., n] ascending
    j = jnp.broadcast_to(jnp.arange(n + m, dtype=_I32), lead + (n + m,))
    na = searchsorted_last(pa, j, side="right")
    ia = jnp.clip(na - 1, 0, n - 1)
    from_a = jnp.take_along_axis(pa, ia, axis=-1) == j
    # one fused source index into the concatenation, then one gather per
    # payload: a where() over six separate gathers kept XLA CPU from
    # fusing them cleanly (~4x slower measured at [8, 2050+1024])
    src = jnp.where(from_a, ia, n + jnp.clip(j - na, 0, m - 1))
    cat = lambda x, y: jnp.broadcast_to(             # noqa: E731
        jnp.concatenate([x, y], axis=-1), lead + (n + m,))
    ok = jnp.take_along_axis(cat(ak, bk), src, axis=-1)
    ov = jnp.take_along_axis(cat(av, bv), src, axis=-1)
    of = jnp.take_along_axis(cat(af, bf), src, axis=-1)
    return ok, ov, of


def _shift_right(x, d: int, fill):
    """x moved right by a static d along the last axis, `fill` entering."""
    head = jnp.full(x.shape[:-1] + (d,), fill, x.dtype)
    return jnp.concatenate([head, x[..., :x.shape[-1] - d]], axis=-1)


def _expand_by_shifts(shift, xs, width: int, max_shift: int):
    """Move element i of each [..., n] row of `xs` right by shift[i] into
    a [..., width] row; returns the moved rows and the mask of the slots
    that hold an element.

    ``shift`` must be nondecreasing along the row, at most ``max_shift``
    (static), and keep every ``i + shift[i] < width``.  One stage per
    shift bit, highest first: an element whose bit t is set moves right
    by 2**t.  After stage t element i sits at i + (shift[i] with the bits
    below t cleared), strictly increasing in i, so no two elements ever
    meet and a stage is "incoming, else vacated, else stay" — a static
    shift and a select, with no gather, scatter or loop."""
    n = shift.shape[-1]
    lead = shift.shape[:-1]
    tail = lambda x, fill: jnp.concatenate(                   # noqa: E731
        [jnp.broadcast_to(x, lead + (n,)),
         jnp.full(lead + (width - n,), fill, x.dtype)], axis=-1)
    c = tail(shift.astype(_I32), -1)            # remaining shift; -1 empty
    xs = [tail(x, 0) for x in xs]
    for t in reversed(range(max_shift.bit_length())):
        d = 1 << t
        go = (c >= 0) & ((c & d) != 0)
        inc = _shift_right(go, d, False)
        c = jnp.where(inc, _shift_right(c, d, -1), jnp.where(go, -1, c))
        xs = [jnp.where(inc, _shift_right(x, d, 0), x) for x in xs]
    return xs, c >= 0


def _merge_sorted_shift(ak, av, af, bk, bv, bf):
    """Gather-free rank merge (ties a-first), the jnp path of
    :func:`merge_sorted`.

    a[i] lands at i + #{b < a[i]} and b[k] at k + #{a <= b[k]}.  Both
    co-ranks are counted by compare-all, not searched, and each stream
    is moved to its ranks by :func:`_expand_by_shifts`
    (⌈log2(m+1)⌉ stages for a, ⌈log2(n+1)⌉ for b, each over n + m
    slots); the two land on complementary slots.  Every step is
    elementwise: XLA:TPU runs a data-dependent gather one index at a
    time, so at the combine pass's 131,072 + 1,024 the searchsorted
    rounds and gathers of :func:`_merge_sorted_corank` took ~30 ms on a
    v5e.  Only compares, static slices and selects, so it also lowers
    inside a kernel body.  Bit-identical to ref.ref_merge_sorted for any
    n, m and equal leading dims; the counting costs n * m compares.
    """
    n, m = ak.shape[-1], bk.shape[-1]
    ca = _searchsorted_compare_all(bk, ak, side="left")
    cb = _searchsorted_compare_all(ak, bk, side="right")
    (ka, va, fa), from_a = _expand_by_shifts(ca, (ak, av, af), n + m, m)
    (kb, vb, fb), _ = _expand_by_shifts(cb, (bk, bv, bf), n + m, n)
    return (jnp.where(from_a, ka, kb), jnp.where(from_a, va, vb),
            jnp.where(from_a, fa, fb))


def merge_sorted(ak, av, af, bk, bv, bf, *, tile: int = 128,
                 backend=None):
    """Merge two sorted INF-padded streams; ties resolve a-first.

    Accepts any equal leading dims (lane-major).  Pallas path: payloads
    ride a f32 matmul, so |val| must be < 2**24 (validated here for
    concrete inputs), and n+m must be even (the output is tiled; the tile
    shrinks to the largest power-of-two divisor, and an odd total has
    none); leading dims map onto the kernel grid via ``jax.vmap`` of the
    ``pallas_call`` (one compiled program, grid-prefixed — not one lane
    at a time).
    """
    bk_ = _coerce(backend)
    if not bk_.is_pallas:
        return _merge_sorted_shift(ak, av, af, bk, bv, bf)
    _check_val_bound(av, bv)
    total = ak.shape[-1] + bk.shape[-1]
    if total % 2:
        # an odd total has no power-of-two tiling: the shrink loop below
        # would previously divide tile to 0 and ZeroDivisionError out
        raise ValueError(
            f"merge_sorted(pallas) needs an even total length to tile the "
            f"output; got n+m={total}. Pad one input by one slot or use "
            f"the jnp backend.")
    while total % tile:
        tile = max(tile // 2, 1)
    kern = lambda *xs: merge_sorted_kvf(*xs, tile=tile,      # noqa: E731
                                        interpret=bk_.interpret)
    lead = ak.shape[:-1]
    args = (ak, av.astype(_I32), af.astype(_I32),
            bk, bv.astype(_I32), bf.astype(_I32))
    if lead:
        args = tuple(x.reshape((-1,) + x.shape[len(lead):]) for x in args)
        ok, ov, of = jax.vmap(kern)(*args)
        return (ok.reshape(lead + ok.shape[1:]),
                ov.reshape(lead + ov.shape[1:]),
                of.reshape(lead + of.shape[1:]))
    return kern(*args)


def select_threshold(keys, k, *, backend=None):
    """(tau, n_below) with tau the k-th smallest of keys (INF-padded)."""
    bk = _coerce(backend)
    if not bk.is_pallas:
        return ref.ref_select_threshold(keys, k)
    return radix_select_threshold(keys, jnp.asarray(k, _I32),
                                  interpret=bk.interpret)


def _radix_select_sorted(flat, flatv, k, k_max: int, cand=None, *,
                         bk: KernelBackend):
    """Shared pallas selection core: radix threshold -> tie-rank split ->
    cumsum compaction -> bitonic sort of the k_max survivors.

    `cand` optionally masks elements that provably cannot be selected
    (splitter-directory pruning); it never changes the result, only trims
    the tie-rank scan.  `bk` is the caller's (pallas) KernelBackend —
    threaded so the interpret choice resolved at config construction
    reaches the inner kernels.  Returns (out_k sorted INF-padded, out_v
    -1-padded, sel — the exact selected positions in `flat`).
    """
    tau, n_below = select_threshold(flat, k, backend=bk)
    below = flat < tau
    eq = flat == tau
    if cand is not None:
        below &= cand
        eq &= cand
    eq_rank = jnp.cumsum(eq.astype(_I32)) - 1
    sel = below | (eq & (eq_rank < (k - n_below)))
    pos = jnp.where(sel, jnp.cumsum(sel.astype(_I32)) - 1, k_max)
    out_k = jnp.full((k_max,), INF, flat.dtype).at[pos].set(flat,
                                                            mode="drop")
    out_v = jnp.full((k_max,), -1, _I32).at[pos].set(flatv.astype(_I32),
                                                     mode="drop")
    zeros = jnp.zeros((k_max,), _I32)
    out_k, out_v, _ = sort_kvf(out_k, out_v, zeros, backend=bk)
    return out_k, out_v, sel


def sorted_runs_gather(keys2d, vals2d, counts, out_len: int):
    """Merge the per-row sorted runs of a range-partitioned store into the
    first `out_len` global ranks — all gathers, no scatter, no global sort.

    Rows are sorted independently (BCAP-wide lanes, vectorized over
    rows); because bucket key ranges are disjoint and ordered, each
    sorted run is a contiguous block of global ranks starting at the
    cumulative count offset, so output rank j gathers from the run that
    contains it.  Accepts any leading dims ([..., NB, BCAP] store,
    [..., NB] counts): the sharded queue's repair passes run all lanes
    through one lane-major call.  Returns (out_k INF-padded, out_v
    -1-padded, rk, rv) where rk/rv are the row-sorted store (reused by
    callers that also need per-row windows, e.g. extraction's survivor
    shift).
    """
    nb, bc = keys2d.shape[-2:]
    lead = keys2d.shape[:-2]
    slot = jnp.arange(bc, dtype=_I32)
    live = slot < counts[..., None]
    mk = jnp.where(live, keys2d, INF)
    mv = jnp.where(live, vals2d, -1).astype(_I32)
    order = argsort_f32_last(mk)
    rk = jnp.take_along_axis(mk, order, axis=-1)
    rv = jnp.take_along_axis(mv, order, axis=-1)
    cum = jnp.cumsum(counts, axis=-1)
    offs = cum - counts
    j = jnp.broadcast_to(jnp.arange(out_len, dtype=_I32),
                         lead + (out_len,))
    row = jnp.clip(searchsorted_last(cum, j, side="right"), 0, nb - 1)
    col = jnp.clip(j - jnp.take_along_axis(offs, row, axis=-1), 0, bc - 1)
    in_run = j < cum[..., nb - 1:nb]
    flat_idx = row * bc + col
    out_k = jnp.where(in_run,
                      jnp.take_along_axis(rk.reshape(lead + (nb * bc,)),
                                          flat_idx, axis=-1), INF)
    out_v = jnp.where(in_run,
                      jnp.take_along_axis(rv.reshape(lead + (nb * bc,)),
                                          flat_idx, axis=-1), -1)
    return out_k, out_v, rk, rv


def select_k_smallest(keys, vals, k, k_max: int, *, backend=None):
    """The k smallest (key, val) pairs, sorted ascending, INF-padded to k_max.

    Pallas path: radix threshold (O(32 L)) + cumsum compaction + bitonic
    sort of the k_max survivors — avoids the O(L log L) full sort the jnp
    oracle performs.  k must be <= k_max; k_max a power of two for pallas.
    """
    bk = _coerce(backend)
    if not bk.is_pallas:
        return ref.ref_select_k(keys, vals, k, k_max)
    k = jnp.minimum(jnp.asarray(k, _I32), k_max)
    out_k, out_v, _ = _radix_select_sorted(keys, vals, k, k_max, bk=bk)
    return out_k, out_v


def extract_k_bucketed(keys2d, vals2d, counts, k, k_max: int, *,
                       splitters=None, backend=None):
    """Extract (select + delete) the k smallest pairs from a bucket store.

    The parallel part of the PQ keeps keys in ``[NB, BCAP]`` buckets whose
    key ranges are disjoint and ordered (bucket i's keys all <= bucket
    i+1's — maintained by the splitter directory).  That structure makes
    moveHead extraction *sortless*:

    * jnp path — sort each bucket row independently (BCAP-wide lanes,
      vectorized over rows: O(L log BCAP) compare work, never an
      O(L log L) global sort).  Each sorted run is a contiguous block of
      global ranks, so the k smallest are a gather over run windows, and
      deletion is a left-shift of each run by its selected-prefix length.
      All gathers — XLA CPU serializes scatters, so none are used.
    * pallas path — radix threshold over the flat stream (O(32 L)),
      splitter-directory pruning of buckets that cannot hold survivors,
      cumsum compaction, one bitonic sort of the k_max survivors; the
      store is compacted around the selected slots.

    Args:
      keys2d: [NB, BCAP] f32, rows range-partitioned; slots >= counts[i]
        ignored.
      vals2d: [NB, BCAP] i32 payloads.
      counts: [NB] i32 live slots per row.
      k: traced scalar; clamped to the live total and k_max.
      k_max: static output width (>= any k; power of two for pallas).
      splitters: [NB] f32 optional per-bucket lower bounds (pallas pruning
        only; pruning is a no-op for correctness, it trims the tie-rank
        scan).

    Returns (out_k [k_max] sorted ascending INF-padded, out_v [k_max]
    payloads (-1 padded), new_keys2d, new_vals2d, new_counts) — the new
    store holds exactly the unselected survivors, ranges preserved.

    Leading dims: the jnp path accepts [..., NB, BCAP] stores with a
    per-lane k [...] (lane-major, one call for all lanes); the pallas
    path maps extra leading dims onto the kernel grid via ``jax.vmap``
    of the ``pallas_call``.
    """
    nb, bc = keys2d.shape[-2:]
    lead = keys2d.shape[:-2]
    slot = jnp.arange(bc, dtype=_I32)
    live = slot < counts[..., None]
    total = counts.sum(axis=-1, dtype=_I32)
    k = jnp.minimum(jnp.minimum(jnp.asarray(k, _I32), total), k_max)

    bk = _coerce(backend)
    if not bk.is_pallas:
        out_k, out_v, rk, rv = sorted_runs_gather(keys2d, vals2d, counts,
                                                  k_max)
        j = jnp.arange(k_max, dtype=_I32)
        out_k = jnp.where(j < k[..., None], out_k, INF)
        out_v = jnp.where(j < k[..., None], out_v, -1)
        # deletion: the selected elements are each run's prefix of length
        # clip(k - start, 0, count); survivors = run suffix, shifted left
        offs = jnp.cumsum(counts, axis=-1) - counts   # run start ranks
        nsel = jnp.clip(k[..., None] - offs, 0, counts).astype(_I32)
        new_counts = counts - nsel
        keep = slot < new_counts[..., None]
        src = jnp.clip(slot + nsel[..., None], 0, bc - 1)
        new_k = jnp.where(keep, jnp.take_along_axis(rk, src, axis=-1), INF)
        new_v = jnp.where(keep, jnp.take_along_axis(rv, src, axis=-1), -1)
        return out_k, out_v, new_k, new_v, new_counts

    if k_max & (k_max - 1):
        raise ValueError(f"pallas extract_k_bucketed needs pow2 k_max, "
                         f"got {k_max}")
    if lead:
        fn = functools.partial(_extract_k_bucketed_pallas_1, k_max=k_max,
                               bk=bk)
        flat = lambda x: x.reshape((-1,) + x.shape[len(lead):])  # noqa: E731
        if splitters is None:
            outs = jax.vmap(lambda a, b, c, d: fn(a, b, c, d, None))(
                flat(keys2d), flat(vals2d), flat(counts), flat(k))
        else:
            outs = jax.vmap(fn)(flat(keys2d), flat(vals2d), flat(counts),
                                flat(k), flat(splitters))
        return tuple(o.reshape(lead + o.shape[1:]) for o in outs)
    return _extract_k_bucketed_pallas_1(keys2d, vals2d, counts, k,
                                        splitters, k_max=k_max, bk=bk)


def _extract_k_bucketed_pallas_1(keys2d, vals2d, counts, k, splitters, *,
                                 k_max: int, bk: KernelBackend):
    """Single-store pallas extraction body (see extract_k_bucketed)."""
    nb, bc = keys2d.shape
    slot = jnp.arange(bc, dtype=_I32)[None, :]
    live = slot < counts[:, None]
    mk = jnp.where(live, keys2d, INF)
    mv = jnp.where(live, vals2d, -1).astype(_I32)
    if splitters is not None:
        # directory pruning: bucket b's elements all have global rank >=
        # its cumulative start offset (ranges are disjoint and ordered by
        # the splitter directory), so a bucket starting at rank >= k can
        # contain no selected element — and because candidate buckets are
        # a prefix of the flat order, pruning preserves the tie-rank
        # selection order exactly.
        offs = jnp.cumsum(counts) - counts
        cand = jnp.broadcast_to((offs < k)[:, None], (nb, bc)).reshape(-1)
    else:
        cand = None
    out_k, out_v, sel = _radix_select_sorted(
        mk.reshape(-1), mv.reshape(-1), k, k_max, cand, bk=bk)
    # compact each row around the selected slots
    sel2 = sel.reshape(nb, bc)
    keep = live & ~sel2
    cpos = jnp.cumsum(keep.astype(_I32), axis=-1) - 1
    cpos = jnp.where(keep, cpos, bc)
    rows = jnp.arange(nb, dtype=_I32)[:, None]
    new_k = jnp.full((nb, bc), INF, keys2d.dtype).at[rows, cpos].set(
        mk, mode="drop")
    new_v = jnp.full((nb, bc), -1, _I32).at[rows, cpos].set(mv, mode="drop")
    new_counts = keep.sum(axis=-1, dtype=_I32)
    return out_k, out_v, new_k, new_v, new_counts
