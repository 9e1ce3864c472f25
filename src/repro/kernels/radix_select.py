"""Pallas TPU kernel: radix threshold selection (k-th smallest of a stream).

``SL::moveHead()`` detaches the ``detach_n`` smallest keys of the parallel
part.  A full sort of the flattened buckets is O(L log L) and touches every
element log L times; instead we find the k-th-smallest *threshold* with a
32-round MSB-first radix scan over the monotone float→uint32 transform —
O(32·L) vector work, no data movement — and then compact/sort only the ~k
selected elements (bitonic, in ``ops.select_k_smallest``).

The whole stream lives in one VMEM block (L ≤ ~2M keys = 8 MiB); each radix
round is a masked popcount, i.e. a full-width VPU reduction.  The loop
carries (prefix, remaining_k) as scalars.

Float→uint32 monotone map: negative floats bit-invert, positives set the
sign bit — total order matches float order, INF sorts above all finite.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_I32 = jnp.int32
_U32 = jnp.uint32


def _to_sortable_u32(x):
    u = jax.lax.bitcast_convert_type(x, _U32)
    neg = (u >> 31) != 0
    return jnp.where(neg, ~u, u | jnp.uint32(0x80000000))


def _from_sortable_u32(u):
    neg = (u >> 31) == 0            # originally negative
    bits = jnp.where(neg, ~u, u & jnp.uint32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _kernel(keys_ref, k_ref, tau_ref, nbelow_ref):
    u = _to_sortable_u32(keys_ref[...])
    k = k_ref[0]

    def round_(i, carry):
        prefix, remaining = carry
        b = 31 - i
        high_mask = ~((jnp.uint32(2) << b) - jnp.uint32(1))  # wraps at b=31
        matched = (u & high_mask) == prefix
        bit0 = ((u >> b) & jnp.uint32(1)) == 0
        cnt0 = jnp.sum((matched & bit0).astype(_I32))
        take1 = remaining > cnt0
        prefix = prefix | jnp.where(take1, jnp.uint32(1) << b,
                                    jnp.uint32(0))
        remaining = jnp.where(take1, remaining - cnt0, remaining)
        return prefix, remaining

    prefix, _ = jax.lax.fori_loop(
        0, 32, round_, (jnp.uint32(0), k))
    tau = _from_sortable_u32(prefix)
    n_below = jnp.sum((u < prefix).astype(_I32))
    tau = jnp.where(k > 0, tau, -jnp.inf)
    n_below = jnp.where(k > 0, n_below, 0)
    tau_ref[0] = tau
    nbelow_ref[0] = n_below


@functools.partial(jax.jit, static_argnames=("interpret",))
def radix_select_threshold(keys, k, *, interpret: bool):
    """(tau, n_below) such that tau is the k-th smallest key of `keys`.

    keys: [L] f32 (INF-padded) or [NB, BCAP] bucket rows (flattened
    internally — the threshold is order-independent); k: scalar i32 with
    0 <= k <= #finite-keys.

    Edge guarantees (pinned by tests/test_kernels.py):
      * k = 0            -> (tau=-inf, n_below=0): nothing selected.
      * k > #finite      -> tau=INF, n_below=#finite (callers clamp k).
      * all-INF stream   -> tau=INF for any k > 0.
      * negative keys    -> exact (the float->uint32 map is monotone over
                            the full float range, including -0.0/-INF).
      * ties at tau      -> n_below counts strictly-below only; selecting
                            all < tau plus (k - n_below) == tau yields
                            exactly k (the eq_rank split in
                            ops.select_k_smallest / select_k_bucketed).
    """
    if keys.ndim == 2:
        keys = keys.reshape(-1)
    length = keys.shape[0]
    k_arr = jnp.asarray(k, _I32).reshape((1,))
    full = lambda: (0,)  # noqa: E731
    tau, nbelow = pl.pallas_call(
        _kernel,
        in_specs=[pl.BlockSpec((length,), full),
                  pl.BlockSpec((1,), full)],
        out_specs=[pl.BlockSpec((1,), full), pl.BlockSpec((1,), full)],
        out_shape=[jax.ShapeDtypeStruct((1,), jnp.float32),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        interpret=interpret,
    )(keys, k_arr)
    return tau[0], nbelow[0]
