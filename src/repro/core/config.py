"""Configuration for the batched adaptive priority queue (APEX-Q core).

The constants mirror the paper exactly where the paper gives them:

* ``detach_min=8``, ``detach_max=65536`` — the adaptive ``moveHead()`` size
  bounds (paper §2.1: "adaptively varies between 8 and 65,536").
* ``halve_threshold=1000`` (paper's N), ``double_threshold=100`` (paper's M):
  "if more than N insertions (e.g. N = 1000) occurred in the sequential part
  since the last SL::moveHead(), we halve the number of elements moved;
  otherwise, if less than M insertions (e.g. M = 100) were made, we double
  this number."

Capacities (``a_max``, ``r_max``, ``seq_cap``, ``n_buckets``, ``bucket_cap``)
are the batch-world analogue of thread counts and skiplist node pools; they
are static so that every tick is a single fixed-shape XLA program.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple, Union

from repro.kernels.ops import KernelBackend, resolve_backend

# Sentinel returned for a removeMin() on an empty queue. The paper returns
# MaxInt (Alg. 3 line 2); we return an +inf key and EMPTY_VAL payload.
EMPTY_VAL = -1


@dataclasses.dataclass(frozen=True)
class PQConfig:
    """Static configuration of a :class:`~repro.core.pqueue.BatchPQ`.

    Frozen + hashable so it can be passed as a static argument to ``jax.jit``.
    """

    # --- batch geometry (the "elimination array" width) -------------------
    a_max: int = 256           # max add() ops per tick
    r_max: int = 256           # max removeMin() ops per tick

    # --- kernel backend: "jnp" | "pallas" | "pallas_interpret" | "auto",
    # resolved ONCE here (construction time, never inside jit tracing) to a
    # frozen repro.kernels.ops.KernelBackend that the tick's sort / merge /
    # extract hot paths — and the sharded lane-tick megakernel — dispatch
    # on.  The default "jnp" resolves without touching the JAX runtime, so
    # module-level configs keep the import-then-set-XLA-flags contract.
    backend: Union[KernelBackend, str] = "jnp"

    # --- sequential part ---------------------------------------------------
    seq_cap: int = 4096        # capacity of the sequential (head) part

    # --- parallel part (the bucketed "skiplist" suffix) ---------------------
    n_buckets: int = 64        # key-range buckets (the skiplist "top level")
    bucket_cap: int = 64       # slots per bucket

    # --- adaptive moveHead policy (paper constants) -------------------------
    detach_min: int = 8
    detach_max: int = 65536
    halve_threshold: int = 1000   # paper's N
    double_threshold: int = 100   # paper's M
    detach_init: int = 64

    # --- chopHead policy -----------------------------------------------------
    # Paper: chopHead "if no removeMin() operations are being requested for
    # some time". We count quiet ticks.
    chop_patience: int = 64

    # --- spill policy ---------------------------------------------------------
    # When addSeq() inserts grow the sequential part beyond
    # (seq_cap - a_max - r_max) we spill the largest sequential keys back to
    # the parallel part (a partial chopHead) so the next tick can never
    # overflow. Growth per tick is bounded by a_max.
    @property
    def spill_threshold(self) -> int:
        return self.seq_cap - self.a_max - self.r_max

    # --- derived ---------------------------------------------------------------
    @property
    def par_cap(self) -> int:
        return self.n_buckets * self.bucket_cap

    @property
    def move_k_max(self) -> int:
        """Static output width of the moveHead selection (ops.select_k_bucketed).

        The extraction size is min(max(detach_n, r2), par_count), so it is
        bounded by min(par_cap, max(r_max, detach_max)); rounded up to a
        power of two for the pallas bitonic pass over the survivors.
        """
        bound = min(self.par_cap, max(self.r_max, self.detach_max))
        return 1 << (bound - 1).bit_length()

    @property
    def total_cap(self) -> int:
        return self.par_cap + self.seq_cap

    def __post_init__(self) -> None:
        # canonicalize the backend spelling eagerly: validation + the
        # jax.default_backend() probe (for "pallas") happen here,
        # outside any trace, so the compiled tick's cache key carries the
        # resolved choice (dataclasses.replace re-runs this; a resolved
        # KernelBackend passes through unchanged)
        object.__setattr__(self, "backend", resolve_backend(self.backend))
        if self.a_max <= 0 or self.r_max <= 0:
            raise ValueError("a_max and r_max must be positive")
        if self.seq_cap < self.a_max + self.r_max + 2:
            raise ValueError(
                f"seq_cap={self.seq_cap} too small; needs headroom of "
                f"a_max+r_max={self.a_max + self.r_max}"
            )
        if self.detach_min < 1 or self.detach_max < self.detach_min:
            raise ValueError("bad detach bounds")
        if self.detach_init < self.detach_min or self.detach_init > self.detach_max:
            raise ValueError("detach_init out of bounds")
        if self.n_buckets < 1 or self.bucket_cap < 1:
            raise ValueError("bad bucket geometry")


# A paper-faithful production configuration: full detach range, generous
# structure capacity. Used by the dry-run and the serving engine.
PRODUCTION = PQConfig(
    a_max=1024,
    r_max=1024,
    seq_cap=1 << 17,          # 131072 >= detach_max + a_max + r_max
    n_buckets=1024,
    bucket_cap=1024,
    detach_min=8,
    detach_max=65536,
    halve_threshold=1000,
    double_threshold=100,
    detach_init=1024,
)

# A small configuration for CPU tests and benchmarks.
SMALL = PQConfig(
    a_max=64,
    r_max=64,
    seq_cap=512,
    n_buckets=16,
    bucket_cap=32,
    detach_min=8,
    detach_max=256,
    detach_init=32,
    halve_threshold=1000,
    double_threshold=100,
    chop_patience=16,
)


def tick_shapes(cfg: PQConfig) -> Tuple[Tuple[int], Tuple[int]]:
    """(add batch shape, remove result shape) for one tick."""
    return (cfg.a_max,), (cfg.r_max,)
