"""Pallas TPU kernel: bitonic co-sort of (keys, vals, flags) rows.

This is the sort at the heart of the batched elimination pass (the paper's
"loop over the elimination array" becomes one data-parallel sorting
network).  TPU adaptation notes (DESIGN.md §2):

* A sorting *network* (bitonic) instead of a comparison sort: every
  compare-exchange stage is a full-width vector op on the VPU — no data
  dependent control flow, no gathers.
* The idx^stride partner exchange is expressed as a reshape to
  ``(groups, 2, stride)`` and lane-wise min/max — pure layout + vector ops,
  no dynamic indexing.
* Grid = rows; each row's (keys, vals, flags) triple is one VMEM-resident
  block.  N (pow2) up to 8192 keeps the working set ≤ ~96 KiB/row, far
  under the ~16 MiB VMEM budget, leaving room for double buffering.

Stages are unrolled statically: log2(N)·(log2(N)+1)/2 compare-exchange
sweeps (78 for N=4096).

Mosaic refuses this kernel for v5e: a ``(1, n)`` row block is not
(8, 128)-aligned (tests/test_tpu_compile.py pins the refusal).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_I32 = jnp.int32


def _cmp_exchange(keys, vals, flags, stage_k: int, stride: int, n: int):
    """One bitonic compare-exchange sweep at `stride` within stage 2^k."""
    g = n // (2 * stride)
    kk = keys.reshape(g, 2, stride)
    vv = vals.reshape(g, 2, stride)
    ff = flags.reshape(g, 2, stride)

    # block g starts at index g*2*stride; direction flips with bit `stage_k`
    base = jax.lax.broadcasted_iota(_I32, (g, 1), 0) * (2 * stride)
    desc = ((base >> stage_k) & 1) == 1

    a_k, b_k = kk[:, 0], kk[:, 1]
    swap = jnp.where(desc, a_k < b_k, a_k > b_k)

    lo_k = jnp.where(swap, b_k, a_k)
    hi_k = jnp.where(swap, a_k, b_k)
    lo_v = jnp.where(swap, vv[:, 1], vv[:, 0])
    hi_v = jnp.where(swap, vv[:, 0], vv[:, 1])
    lo_f = jnp.where(swap, ff[:, 1], ff[:, 0])
    hi_f = jnp.where(swap, ff[:, 0], ff[:, 1])

    keys = jnp.stack([lo_k, hi_k], axis=1).reshape(n)
    vals = jnp.stack([lo_v, hi_v], axis=1).reshape(n)
    flags = jnp.stack([lo_f, hi_f], axis=1).reshape(n)
    return keys, vals, flags


def _sort_network(keys, vals, flags, n: int):
    n_log = n.bit_length() - 1
    for k in range(1, n_log + 1):
        for j in range(k - 1, -1, -1):
            keys, vals, flags = _cmp_exchange(keys, vals, flags, k, 1 << j, n)
    return keys, vals, flags


def _kernel(keys_ref, vals_ref, flags_ref, ok_ref, ov_ref, of_ref, *, n: int):
    keys = keys_ref[0, :]
    vals = vals_ref[0, :]
    flags = flags_ref[0, :]
    keys, vals, flags = _sort_network(keys, vals, flags, n)
    ok_ref[0, :] = keys
    ov_ref[0, :] = vals
    of_ref[0, :] = flags


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort_kvf(keys, vals, flags, *, interpret: bool):
    """Co-sort each row of (keys, vals, flags) by key ascending.

    Shapes: [rows, n] with n a power of two. keys f32, vals i32, flags i32.
    NOTE: the network is not stable; equal keys may permute their payloads
    (the PQ semantics only require multiset agreement for equal keys).
    """
    rows, n = keys.shape
    if n & (n - 1):
        raise ValueError(f"bitonic length must be a power of two, got {n}")
    kernel = functools.partial(_kernel, n=n)
    return pl.pallas_call(
        kernel,
        grid=(rows,),
        in_specs=[
            pl.BlockSpec((1, n), lambda r: (r, 0)),
            pl.BlockSpec((1, n), lambda r: (r, 0)),
            pl.BlockSpec((1, n), lambda r: (r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n), lambda r: (r, 0)),
            pl.BlockSpec((1, n), lambda r: (r, 0)),
            pl.BlockSpec((1, n), lambda r: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, n), keys.dtype),
            jax.ShapeDtypeStruct((rows, n), vals.dtype),
            jax.ShapeDtypeStruct((rows, n), flags.dtype),
        ],
        interpret=interpret,
    )(keys, vals, flags)
