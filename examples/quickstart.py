"""Quickstart: the adaptive priority queue with elimination and combining.

Runs on a single CPU device; ~10 seconds.

    PYTHONPATH=src python examples/quickstart.py

Engines are built through the unified factory (repro.core.factory): one
``EngineSpec`` names the engine kind — ``pqe`` (the paper's combined
queue, used here), ``sharded`` (L relaxed lanes), ``dist`` / ``elastic``
(device mesh, fault tolerance), or ``adaptive`` (a workload controller
that picks between them at runtime).  The last section measures what
relaxation *costs*: the rank-error meter (repro.quality, DESIGN.md §12)
replays each engine's served stream against the exact reference.
"""

import numpy as np
import jax.numpy as jnp

from repro.core import EngineSpec, PQConfig, make_engine


def main() -> None:
    # a small queue: 64-op ticks, a 512-slot sequential head, 16 buckets
    base = PQConfig(a_max=64, r_max=64, seq_cap=512, n_buckets=16,
                    bucket_cap=64, detach_min=8, detach_max=256,
                    detach_init=32)
    eng = make_engine(EngineSpec(engine="pqe", width=64, base=base))
    state = eng.init(seed=0)
    rng = np.random.default_rng(0)

    print("== insert three batches of 64 random keys ==")
    for b in range(3):
        keys = rng.uniform(0, 1000, 64).astype(np.float32)
        ak = jnp.asarray(keys)
        av = jnp.arange(64, dtype=jnp.int32) + b * 64
        mask = jnp.ones((64,), bool)
        state, _ = eng.tick(state, ak, av, mask, jnp.asarray(0))
    print(f"queue size: {int(eng.size(state))}"
          f"  min={float(state.min_value):.2f}"
          f"  lastSeq={float(state.last_seq):.2f}"
          f"  detach_n={int(state.detach_n)}")

    print("\n== a combined tick: 32 adds + 32 removeMin ==")
    keys = rng.uniform(0, 1000, 32).astype(np.float32)
    ak = jnp.full((64,), jnp.inf, jnp.float32).at[:32].set(
        jnp.asarray(keys))
    av = jnp.arange(64, dtype=jnp.int32) + 1000
    mask = jnp.zeros((64,), bool).at[:32].set(True)
    state, res = eng.tick(state, ak, av, mask, jnp.asarray(32))
    served = np.asarray(res.rm_keys)[np.asarray(res.rm_served)]
    print(f"removed the {len(served)} smallest keys: "
          f"{np.sort(served)[:8].round(1)} ...")

    s = eng.stats(state)
    print("\n== per-path breakdown (the paper's Figs. 7-8) ==")
    print(f" adds eliminated immediately : {int(s.add_imm_elim)}")
    print(f" adds eliminated after aging : {int(s.add_upc_elim)}")
    print(f" adds combined (server)      : {int(s.add_seq)}")
    print(f" adds inserted in parallel   : {int(s.add_par)}")
    print(f" removes served from head    : {int(s.rm_seq)}")
    print(f" moveHead / chopHead events  : {int(s.n_movehead)}"
          f" / {int(s.n_chophead)}")

    print("\n== kernel backend: config, not per-call (DESIGN.md §13) ==")
    # backend selection rides the spec and resolves ONCE at engine
    # construction — "jnp" (reference, and the chip path), "pallas"
    # (fused lanes-in-grid megakernel via Mosaic; TPU only, and Mosaic
    # refuses it for v5e today), "pallas_interpret" (the same kernel,
    # interpreter-executed — the off-TPU validation mode used here), or
    # "auto" (jnp; the PQ_BACKEND env var overrides).  Same stream, bit-identical
    # serves on any backend — that contract is CI-pinned
    # (tests/test_lane_megakernel.py).
    fused = make_engine(EngineSpec(engine="pqe", width=64, base=base,
                                   backend="pallas_interpret"))
    print(f" resolved at construction: {fused.cfg.backend}")
    fstate = fused.init(seed=0)
    fkeys = rng.uniform(0, 1000, 64).astype(np.float32)
    fstate, _ = fused.tick(fstate, jnp.asarray(fkeys),
                           jnp.arange(64, dtype=jnp.int32),
                           jnp.ones((64,), bool), jnp.asarray(0))
    fstate, fres = fused.tick(fstate,
                              jnp.full((64,), jnp.inf, jnp.float32),
                              jnp.zeros((64,), jnp.int32),
                              jnp.zeros((64,), bool), jnp.asarray(8))
    fserved = np.sort(np.asarray(fres.rm_keys)[np.asarray(fres.rm_served)])
    assert np.array_equal(fserved, np.sort(fkeys)[:8])
    print(f" megakernel served the exact 8 smallest: {fserved.round(1)}")

    print("\n== relaxation quality: rank error vs the exact reference ==")
    # the meter replays each engine's own (adds, served) stream against
    # the instantaneous exact union (DESIGN.md §12): pqe is exact, so
    # it scores identically 0; relaxed lanes trade rank error for
    # speed, bounded by relax_bound(r) - r
    from repro.quality import measure_engine, probe_stream, warm_keys

    warm = warm_keys(200)
    ak, av, am, rc = probe_stream(64, 0.5, 10)
    n_rm = int(rc[0])
    for name, spec in (
        ("pqe (exact)  ", EngineSpec(engine="pqe", width=64, base=base)),
        ("sharded L=4  ", EngineSpec(engine="sharded", width=64, lanes=4)),
    ):
        q = make_engine(spec)
        # measure_engine warms the fresh engine with the same keys it
        # preloads into the reference union, then scores every tick
        qs = measure_engine(q, ak, av, am, rc, warm_keys=warm)
        envelope = q.relax_bound(n_rm) - n_rm
        print(f" {name}: rank_err p50={qs['rank_err_p50']:5.1f}"
              f" p99={qs['rank_err_p99']:6.1f}"
              f" max={qs['rank_err_max']:4d}"
              f" (envelope {envelope})"
              f"  stale_max={qs['stale_max']}")


if __name__ == "__main__":
    main()
