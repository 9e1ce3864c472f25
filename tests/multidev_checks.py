"""Multi-device checks — executed in a SUBPROCESS with 8 fake devices
(tests/test_multidev.py drives this; device count locks at first jax
init, so these cannot run in the main pytest process).

Checks:
 1. DistShardedQueue conservation + relax bound (D=8 x l=2 lanes)
 2. DistShardedQueue(D=8, l=1) == single-device sharded_L8 (same stream)
 3. elastic resize: device killed mid-stream, lanes re-shard over the
    7 survivors, conservation + shrunk-L relax bound hold throughout
 4. shard_map EP MoE == local MoE (no-drop regime)
 5. sharded train_step executes on a (2,4) mesh, ZeRO+FSDP specs applied
 6. sharded decode step executes on a (2,4) mesh
 7. chip_smoke.py's four-device phase at a small size: dist D=4 ==
    sharded L=8 every tick, then remove_device conserves the multiset

Exit codes: 0 ok, 42 SKIP (host device count could not be forced — the
parent pytest harness turns this into a clean skip), anything else is a
failure whose traceback the parent surfaces from stderr.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses  # noqa: E402
import sys          # noqa: E402
import traceback    # noqa: E402

import numpy as np  # noqa: E402
import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.dist.sharding import make_mesh  # noqa: E402

SKIP_EXIT = 42


def _require_forced_devices(n: int = 8) -> None:
    ndev = len(jax.devices())
    if ndev != n:
        print(f"SKIP: host device count is {ndev}, wanted {n} — "
              f"--xla_force_host_platform_device_count not honored on "
              f"platform={jax.default_backend()!r}", file=sys.stderr)
        sys.exit(SKIP_EXIT)


def _dist_queue(n_devices, lanes_per_device, width, base, spare_devices=0):
    from repro.core.factory import EngineSpec, make_engine

    return make_engine(EngineSpec(
        engine="dist", width=width, base=base,
        lanes=n_devices * lanes_per_device, n_devices=n_devices,
        lanes_per_device=lanes_per_device, spare_devices=spare_devices))


def check_dist_sharded():
    """Conservation + relax bound of the lanes-over-devices queue at
    D=8 x l=2 (the subprocess twin of tests/test_dist_sharded.py, which
    needs a forced multi-device process to reach D>1)."""
    from repro.core.config import PQConfig

    W = 64
    base = PQConfig(a_max=W, r_max=W, seq_cap=512, n_buckets=16,
                    bucket_cap=32, detach_min=4, detach_max=64,
                    detach_init=8, chop_patience=8)
    q = _dist_queue(8, 2, W, base)
    state = q.init(seed=2)
    rng = np.random.default_rng(0)
    mirror = []
    next_val = 0
    load_cap = q.cfg.shard.n_lanes * q.cfg.shard.lane.par_cap // 2
    for t in range(30):
        n_add = min(int(rng.integers(0, W + 1)), load_cap - len(mirror))
        n_rm = int(rng.integers(0, W // 2 + 1))
        keys = np.round(rng.uniform(0, 1000, n_add), 3).astype(np.float32)
        ak = np.full((W,), np.inf, np.float32)
        av = np.full((W,), -1, np.int32)
        mask = np.zeros((W,), bool)
        ak[:n_add] = keys
        av[:n_add] = np.arange(next_val, next_val + n_add)
        mask[:n_add] = True
        next_val += n_add

        combined = sorted(mirror + keys.tolist())
        c = q.relax_bound(n_rm)
        cutoff = combined[c - 1] if c <= len(combined) else np.inf

        state, res = q.tick(state, jnp.asarray(ak), jnp.asarray(av),
                            jnp.asarray(mask), n_rm)
        got = np.asarray(res.rm_keys)[np.asarray(res.rm_served)]
        assert len(got) <= n_rm, t
        for k in got:
            assert k <= cutoff, (t, k, c, cutoff)
            combined.remove(float(np.float32(k)))
        mirror = combined
        assert int(state.n_router_dropped) == 0, t
        assert int(q.size(state)) == len(mirror), t
    print("OK dist_sharded")


def check_dist_equiv():
    """dist(8 devices x 1 lane) serves the same multiset as
    single-device sharded_L8 on the same op stream (PR-4 acceptance)."""
    from repro.core import sharded as shq
    from repro.core.config import PQConfig

    W = 64
    base = PQConfig(a_max=W, r_max=W, seq_cap=512, n_buckets=16,
                    bucket_cap=32, detach_min=4, detach_max=64,
                    detach_init=8, chop_patience=8)
    q = _dist_queue(8, 1, W, base)
    scfg = q.cfg.shard
    dstate = q.init(seed=1)
    sstate = shq.init(scfg, seed=1)
    rng = np.random.default_rng(3)
    next_val = 0
    for t in range(25):
        n_add = int(rng.integers(0, W + 1))
        n_rm = int(rng.integers(0, W // 2 + 1))
        ak = np.full((W,), np.inf, np.float32)
        av = np.full((W,), -1, np.int32)
        mask = np.zeros((W,), bool)
        ak[:n_add] = np.round(rng.uniform(0, 1000, n_add),
                              3).astype(np.float32)
        av[:n_add] = np.arange(next_val, next_val + n_add)
        mask[:n_add] = True
        next_val += n_add
        args = (jnp.asarray(ak), jnp.asarray(av), jnp.asarray(mask))
        dstate, dres = q.tick(dstate, *args, n_rm)
        sstate, sres = shq.tick(scfg, sstate, *args, jnp.asarray(n_rm))
        dk = np.sort(np.asarray(dres.rm_keys)[np.asarray(dres.rm_served)])
        sk = np.sort(np.asarray(sres.rm_keys)[np.asarray(sres.rm_served)])
        assert np.array_equal(dk, sk), (t, dk, sk)
        assert int(q.size(dstate)) == int(shq.size(sstate)), t
    assert int(q.stats(dstate).n_preroute_elim) == \
        int(shq.stats(sstate).n_preroute_elim)
    print("OK dist_equiv")


def check_dist_resize():
    """Kill a device mid-stream: lanes re-shard over the 7 survivors,
    conservation and the shrunk-L relax bound hold from the first
    post-resize tick (the subprocess twin of tests/test_dist_resize.py)."""
    from repro.core import distributed as dq
    from repro.core.config import PQConfig

    W = 64
    base = PQConfig(a_max=W, r_max=W, seq_cap=512, n_buckets=16,
                    bucket_cap=32, detach_min=4, detach_max=64,
                    detach_init=8, chop_patience=8)
    q = _dist_queue(8, 1, W, base, spare_devices=1)
    state = q.init(seed=6)
    rng = np.random.default_rng(6)
    mirror = []
    next_val = 0
    load_cap = (q.cfg.shard.n_lanes - 1) * q.cfg.shard.lane.par_cap // 2
    for t in range(20):
        if t == 7:   # the death verdict: drop device 3 of 8
            pre = int(q.size(state))
            q, state = q.remove_device(state, 3)
            assert q.cfg.n_devices == 7 and q.cfg.shard.n_lanes == 7
            assert int(q.size(state)) == pre == len(mirror), t
        n_add = min(int(rng.integers(0, W + 1)),
                    max(0, load_cap - len(mirror)))
        n_rm = int(rng.integers(0, W // 2 + 1))
        keys = np.round(rng.uniform(0, 1000, n_add), 3).astype(np.float32)
        ak = np.full((W,), np.inf, np.float32)
        av = np.full((W,), -1, np.int32)
        mask = np.zeros((W,), bool)
        ak[:n_add] = keys
        av[:n_add] = np.arange(next_val, next_val + n_add)
        mask[:n_add] = True
        next_val += n_add

        combined = sorted(mirror + keys.tolist())
        c = q.relax_bound(n_rm)
        cutoff = combined[c - 1] if c <= len(combined) else np.inf

        state, res = q.tick(state, jnp.asarray(ak), jnp.asarray(av),
                            jnp.asarray(mask), n_rm)
        got = np.asarray(res.rm_keys)[np.asarray(res.rm_served)]
        assert len(got) <= n_rm, t
        for k in got:
            assert k <= cutoff, (t, k, c, cutoff)
            combined.remove(float(np.float32(k)))
        mirror = combined
        assert int(state.n_router_dropped) == 0, t
        assert int(q.size(state)) == len(mirror), t
    print("OK dist_resize")


def check_moe_parity():
    from repro.configs import reduced_config
    from repro.dist.sharding import use_mesh
    from repro.models import moe

    cfg = dataclasses.replace(
        reduced_config("qwen3-moe-235b-a22b"), n_experts=8, top_k=2,
        capacity_factor=8.0, dtype="float32")   # no-drop regime
    mesh = make_mesh((2, 4), ("data", "model"))
    params = moe.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                          jnp.float32) * 0.1

    y_local, aux_local = moe._moe_local(params, cfg, x)
    with use_mesh(mesh):
        y_dist, aux_dist = jax.jit(
            lambda p, xx: moe.moe_apply(p, cfg, xx))(params, x)
    np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_dist),
                               rtol=2e-4, atol=2e-5)
    # the Switch aux loss is nonlinear in the token partition (per-shard
    # me/ce then pmean != global); ~0.2% deviation is expected math, not
    # a bug — outputs y match tightly above
    np.testing.assert_allclose(float(aux_local), float(aux_dist),
                               rtol=1e-2)
    print("OK moe_parity")


def check_sharded_train_step():
    from repro.configs import reduced_config
    from repro.dist.sharding import use_mesh
    from repro.launch.train import (TrainConfig, batch_specs,
                                    init_train_state, make_train_step,
                                    state_shardings)

    cfg = dataclasses.replace(reduced_config("gemma-2b"), n_layers=2,
                              vocab=512)
    tcfg = TrainConfig(n_micro=2, fsdp=True, zero1=True)
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        state = init_train_state(cfg, jax.random.PRNGKey(0), tcfg)
        st_shape = jax.eval_shape(lambda: state)
        st_sh = state_shardings(cfg, tcfg, mesh, st_shape)
        state = jax.tree.map(jax.device_put, state, st_sh)
        step = jax.jit(make_train_step(cfg, tcfg, mesh),
                       in_shardings=(st_sh, batch_specs(cfg, mesh)),
                       donate_argnums=(0,))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0,
                                    cfg.vocab)
        batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}
        batch = jax.device_put(batch, batch_specs(cfg, mesh))
        state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"])), metrics
    print("OK sharded_train_step")


def check_sharded_decode():
    from repro.configs import reduced_config
    from repro.dist.sharding import use_mesh
    from repro.launch.serve import cache_shardings, params_shardings
    from repro.models import transformer as tf

    cfg = dataclasses.replace(reduced_config("gemma-2b"), n_layers=2,
                              vocab=512)
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        params = tf.init_params(cfg, jax.random.PRNGKey(0))
        caches = tf.init_decode_caches(cfg, 8, 32)
        p_sh = params_shardings(cfg, mesh, jax.eval_shape(lambda: params))
        c_sh = cache_shardings(cfg, mesh, jax.eval_shape(lambda: caches))
        params = jax.tree.map(jax.device_put, params, p_sh)
        caches = jax.tree.map(jax.device_put, caches, c_sh)
        tok = jnp.ones((8, 1), jnp.int32)
        pos = jnp.zeros((8,), jnp.int32)
        logits, caches = jax.jit(
            lambda p, c, t, q: tf.decode_step(cfg, p, t, c, q))(
            params, caches, tok, pos)
        assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    print("OK sharded_decode")


def check_chip_smoke_dist():
    """chip_smoke.dist_phase on 4 of the forced devices, small shapes
    (the chip runs it at W=8192 and 5x10^5 resident keys)."""
    from pathlib import Path

    from repro.core.config import PQConfig

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    import chip_smoke as cs

    base = PQConfig(a_max=64, r_max=64, seq_cap=512, n_buckets=16,
                    bucket_cap=128, detach_min=4, detach_max=64,
                    detach_init=8, chop_patience=8)
    out = cs.dist_phase(jax.devices()[:4], width=128, lanes=8, base=base,
                        resident=600, mix_ticks=20, chunk=5, after_ticks=4,
                        seed=2, clock=cs.CompileClock())
    assert out["devices_after"] == 3, out
    print("OK chip_smoke_dist")


def check_scopes_dist():
    """The lanes-over-devices tick_n, compiled for 4 devices, carries
    every pass scope and the router, pre-route, grant and lane-summary
    gather scopes in its HLO op_name metadata, the two lane-summary
    all-gathers under ``dq.gather``."""
    import re

    from repro.core import obs
    from repro.core.config import PQConfig

    base = PQConfig(a_max=64, r_max=64, seq_cap=512, n_buckets=16,
                    bucket_cap=128, detach_min=4, detach_max=64,
                    detach_init=8, chop_patience=8)
    q = _dist_queue(4, 2, 128, base)
    w, t = q.width, 2
    args = (np.zeros((t, w), np.float32), np.zeros((t, w), np.int32),
            np.zeros((t, w), bool), np.zeros((t,), np.int32),
            np.ones((q.cfg.shard.n_lanes,), np.float32))
    text = q._tick_n.lower(q.init(seed=0), *args).compile().as_text()
    found = {part.rsplit("(", 1)[-1].rstrip(")")
             for name in re.findall(r'op_name="([^"]*)"', text)
             for part in name.split("/")} & set(obs.SCOPES)
    assert found == set(obs.SCOPES), set(obs.SCOPES) - found
    gathers = [re.search(r'op_name="([^"]*)"', ln).group(1)
               for ln in text.splitlines()
               if re.search(r"\sall-gather(-start)?\(", ln)]
    # the two lane-summary gathers; the partitioner adds its own
    # collectives for the result fold after shard_map (not scoped)
    assert sum(obs.DQ_GATHER in g for g in gathers) == 2, gathers
    print("OK scopes_dist")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    checks = {
        "dist": check_dist_sharded,
        "dist_equiv": check_dist_equiv,
        "dist_resize": check_dist_resize,
        "moe": check_moe_parity,
        "train": check_sharded_train_step,
        "decode": check_sharded_decode,
        "chip_smoke_dist": check_chip_smoke_dist,
        "scopes_dist": check_scopes_dist,
    }
    _require_forced_devices()
    try:
        if which == "all":
            for fn in checks.values():
                fn()
        else:
            checks[which]()
    except BaseException:
        # full traceback on stderr even if something upstream replaced
        # sys.excepthook — the parent pytest assertion shows stderr
        traceback.print_exc()
        sys.exit(1)
    print("ALL MULTIDEV OK" if which == "all" else "DONE")
