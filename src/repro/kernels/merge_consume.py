"""Pallas TPU kernel: rank-merge of two sorted streams via one-hot MXU scatter.

The combine stage of the PQ tick merges the sorted sequential part with the
sorted small-key add batch (SL::addSeq + removeMin prefix consumption).  A
scatter with computed indices is hostile to TPU; instead we:

1. compute each element's output *rank* with vectorized counting
   (``pos_a[i] = i + #{b < a[i]}``, ``pos_b[j] = j + #{a <= b[j]}`` — ties
   resolve a-first, making the merge stable across streams), then
2. materialize each output tile as a **one-hot matmul**: build the
   ``(src, tile)`` one-hot matrix from the ranks and contract it against the
   stacked (keys, vals, flags) payload on the MXU.  Scatter-free, fully
   dense, hardware-aligned tiles.

Positions are computed once into VMEM scratch at grid step 0 and reused by
every output tile (the TPU grid is sequential, so scratch carries across
steps).  Payload values ride through an f32 matmul: exact only for
``|val| < 2**24``.  The ops wrapper (``ops._check_val_bound``) rejects
concrete out-of-bound payloads eagerly; traced values are the caller's
contract (the PQ tick's payloads are i32 batch indices, well inside it).

VMEM budget per step: a-window S·T one-hot (e.g. 2048×256 f32 = 2 MiB) +
payloads — comfortably under budget; the count matrix is chunked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_I32 = jnp.int32
_F32 = jnp.float32
_CHUNK = 256  # count-matrix chunk width
_CAP = 3.0e38  # finite stand-in for INF inside the matmul (python literal)


def _count_less(b, a):
    """cnt[i] = #{j : b[j] < a[i]}, chunked over b."""
    n = a.shape[0]
    cnt = jnp.zeros((n,), _I32)
    for c0 in range(0, b.shape[0], _CHUNK):
        bc = b[c0:c0 + _CHUNK]
        cnt = cnt + jnp.sum(
            (bc[None, :] < a[:, None]).astype(_I32), axis=1)
    return cnt


def _count_leq(a, b):
    """cnt[j] = #{i : a[i] <= b[j]}, chunked over a."""
    m = b.shape[0]
    cnt = jnp.zeros((m,), _I32)
    for c0 in range(0, a.shape[0], _CHUNK):
        ac = a[c0:c0 + _CHUNK]
        cnt = cnt + jnp.sum(
            (ac[None, :] <= b[:, None]).astype(_I32), axis=1)
    return cnt


def _kernel(ak_ref, av_ref, af_ref, bk_ref, bv_ref, bf_ref,
            ok_ref, ov_ref, of_ref, pos_a, pos_b, *, tile: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _compute_positions():
        ak = ak_ref[...]
        bk = bk_ref[...]
        n = ak.shape[0]
        m = bk.shape[0]
        pos_a[...] = jax.lax.broadcasted_iota(_I32, (n,), 0) \
            + _count_less(bk, ak)
        pos_b[...] = jax.lax.broadcasted_iota(_I32, (m,), 0) \
            + _count_leq(ak, bk)

    c0 = step * tile
    cols = c0 + jax.lax.broadcasted_iota(_I32, (tile,), 0)

    def scatter_side(pos, k_ref, v_ref, f_ref):
        onehot = (pos[...][:, None] == cols[None, :]).astype(_F32)
        # INF * 0 = NaN would poison the matmul: cap keys to a finite
        # sentinel and decode back after the contraction.
        payload = jnp.stack([
            jnp.minimum(k_ref[...].astype(_F32), _CAP),
            v_ref[...].astype(_F32),
            f_ref[...].astype(_F32),
        ])  # [3, src]
        return jax.lax.dot_general(
            payload, onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=_F32)  # [3, tile]

    out = scatter_side(pos_a, ak_ref, av_ref, af_ref) \
        + scatter_side(pos_b, bk_ref, bv_ref, bf_ref)
    ok_ref[...] = jnp.where(out[0] >= _CAP, jnp.inf, out[0])
    ov_ref[...] = out[1].astype(_I32)
    of_ref[...] = out[2].astype(_I32)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def merge_sorted_kvf(ak, av, af, bk, bv, bf, *, tile: int = 256,
                     interpret: bool):
    """Merge sorted (INF-padded) streams a and b; ties resolve a-first.

    Args: ak/bk f32 sorted ascending, av/bv i32 (|v| < 2**24), af/bf i32.
    Returns merged (keys f32, vals i32, flags i32) of length n+m.

    Caveat (INF padding): both streams are INF-padded; INF==INF ties resolve
    a-first like any tie, so padding merges after all finite keys.
    """
    n, m = ak.shape[0], bk.shape[0]
    total = n + m
    if total % tile:
        raise ValueError(f"n+m={total} must be a multiple of tile={tile}")
    grid = (total // tile,)
    full = lambda r: (0,)  # noqa: E731  — whole-array block each step
    kernel = functools.partial(_kernel, tile=tile)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((n,), full), pl.BlockSpec((n,), full),
                  pl.BlockSpec((n,), full),
                  pl.BlockSpec((m,), full), pl.BlockSpec((m,), full),
                  pl.BlockSpec((m,), full)],
        out_specs=[pl.BlockSpec((tile,), lambda r: (r,)),
                   pl.BlockSpec((tile,), lambda r: (r,)),
                   pl.BlockSpec((tile,), lambda r: (r,))],
        out_shape=[jax.ShapeDtypeStruct((total,), jnp.float32),
                   jax.ShapeDtypeStruct((total,), jnp.int32),
                   jax.ShapeDtypeStruct((total,), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((n,), _I32), pltpu.VMEM((m,), _I32)],
        interpret=interpret,
    )(ak, av, af, bk, bv, bf)
