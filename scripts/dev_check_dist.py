"""Dev check: DistShardedQueue (lanes-over-devices) on 8 fake devices.

Drives the mesh queue against a python multiset mirror (conservation +
relax bound) and against single-device `sharded` on the same op stream
(serve equivalence) — the quick local twin of the CI tests-multidev leg.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python scripts/dev_check_dist.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import sharded as shq
from repro.core.config import PQConfig
from repro.core.factory import EngineSpec, make_engine


def main():
    ndev = len(jax.devices())
    assert ndev == 8, ndev
    W = 64
    base = PQConfig(a_max=W, r_max=W, seq_cap=512, n_buckets=16,
                    bucket_cap=32, detach_min=4, detach_max=64,
                    detach_init=8, chop_patience=8)
    q = make_engine(EngineSpec(engine="dist", width=W, base=base, lanes=16,
                               n_devices=8, lanes_per_device=2))
    scfg = make_engine(EngineSpec(engine="sharded", width=W, base=base,
                                  lanes=16)).cfg
    assert scfg == q.cfg.shard
    dstate = q.init(seed=1)
    sstate = shq.init(scfg, seed=1)

    rng = np.random.default_rng(0)
    mirror = []
    next_val = 0
    for t in range(40):
        n_add = int(rng.integers(0, W + 1))
        n_rm = int(rng.integers(0, W // 2 + 1))
        keys = np.round(rng.uniform(0, 1000, n_add), 3).astype(np.float32)
        ak = np.full((W,), np.inf, np.float32)
        av = np.full((W,), -1, np.int32)
        mask = np.zeros((W,), bool)
        ak[:n_add] = keys
        av[:n_add] = np.arange(next_val, next_val + n_add)
        mask[:n_add] = True
        next_val += n_add
        args = (jnp.asarray(ak), jnp.asarray(av), jnp.asarray(mask))

        combined = sorted(mirror + keys.tolist())
        c = q.relax_bound(n_rm)
        cutoff = combined[c - 1] if c <= len(combined) else np.inf

        dstate, dres = q.tick(dstate, *args, n_rm)
        sstate, sres = shq.tick(scfg, sstate, *args, jnp.asarray(n_rm))

        got = np.sort(np.asarray(dres.rm_keys)[np.asarray(dres.rm_served)])
        ref = np.sort(np.asarray(sres.rm_keys)[np.asarray(sres.rm_served)])
        assert np.array_equal(got, ref), (t, got, ref)   # dist == 1-dev
        for k in got:
            assert k <= cutoff, (t, k, c, cutoff)
            combined.remove(float(np.float32(k)))
        mirror = combined
        assert int(q.size(dstate)) == len(mirror), t

    st = q.stats(dstate)
    print(f"OK dist_sharded: ticks={int(st.n_ticks)} "
          f"preroute_elim={int(st.n_preroute_elim)} "
          f"lane_removes={int(st.lane.n_removes)} "
          f"lane_sizes={np.asarray(q.lane_sizes(dstate)).tolist()}")


if __name__ == "__main__":
    main()
