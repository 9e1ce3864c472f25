"""Run the queue's main path once on a TPU, at deployment depth, and check it.

    PYTHONPATH=src python chip_smoke.py [--seed N] [--chips 4]

With no option it needs one chip and runs three phases through the
entry points a user calls:

* ``pqe`` — ``make_engine`` of the exact queue at the ``PRODUCTION``
  config, loaded to 5x10^5 resident keys by zero-remove ``tick_n``
  chunks, then 200 ticks of the DES hold mix at p_add 0.5.  The served
  stream is replayed against the exact reference
  (``repro.quality.harness``): no rank error, no lost key, and the
  resident multiset equals the reference's.
* ``sharded`` — the same load and mix on the L=8 relaxed queue at
  W=8192: rank error within ``relax_bound(r) - r``, nothing lost.
* ``serve`` — the elastic serving engine on a one-chip mesh
  (``serving.sla.build_engine`` + ``run_sla``): every arrival ends
  served, shed or expired, exactly once.

``--chips 4`` runs only the mesh path: the ``dist`` queue at D=4 (two
lanes per chip, W=8192) against the one-chip ``sharded`` L=8 queue on
the same stream — the same served multiset on every tick — then
``remove_device`` of chip 3 with the resident multiset conserved, and a
few ticks on the 3-chip mesh.

All data comes from ``--seed``.  Every phase prints its compile seconds,
steady wall seconds per tick (timed to ``block_until_ready``) and its
checks; these are smoke figures, not benchmark numbers.  Any failed
check exits non-zero.  On success the last line is the JSON object
``{"ok": true, "device": {...}}`` naming the device JAX ran on.  Without
a TPU the script exits non-zero before any phase runs.

The phases are plain functions of their sizes, so tests and CPU
rehearsals call them directly at small sizes (tests/test_chip_smoke.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

#: resident keys loaded before the mix (ROADMAP: DES event sets and
#: fleet backlogs hold 10^5-10^6)
RESIDENT = 500_000
MIX_TICKS = 200
P_ADD = 0.5
#: ticks per tick_n call: loading and the mix share one compiled program
CHUNK = 50
#: ticks on the shrunk mesh after remove_device
AFTER_TICKS = 10
#: serving rounds before the drain
SERVE_TICKS = 200


class SmokeFailure(RuntimeError):
    """A phase's result broke one of its checks."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds of XLA backend compilation, summed from JAX's own
    monitoring event (tracing is left out: its events nest).  A warm
    persistent cache skips the compile, and the clock then reads ~0."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def _emit(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _stream(width: int, *, resident: int, mix_ticks: int, chunk: int,
            p_add: float, rng):
    """The whole op stream as host arrays ``(keys, vals, mask, rm)`` of
    shape [T, W] ([T] for rm), T a multiple of ``chunk``: zero-remove
    load ticks carrying ``resident`` uniform keys, then the DES hold mix
    (``pq_bench.mix_arrays``).  Returns the stream and the tick index
    the mix starts at."""
    from benchmarks.pq_bench import KEY_HI, mix_arrays

    n_load = -(-resident // width)
    n_load += -n_load % chunk
    if mix_ticks % chunk:
        raise ValueError(f"mix_ticks {mix_ticks} not a multiple of {chunk}")
    lk = np.full((n_load * width,), np.inf, np.float32)
    lk[:resident] = rng.uniform(0, KEY_HI, resident)
    lk = lk.reshape(n_load, width)
    lm = np.isfinite(lk)
    lv = np.broadcast_to(np.arange(width, dtype=np.int32),
                         (n_load, width))
    n_add = int(round(width * p_add))
    n_rm = width - n_add
    mk, mv, mm = mix_arrays(width, n_add, n_rm, mix_ticks, rng, "des",
                            resident=resident)
    rm = np.concatenate([np.zeros(n_load, np.int32),
                         np.full(mix_ticks, n_rm, np.int32)])
    return (np.concatenate([lk, mk]), np.concatenate([lv, mv]),
            np.concatenate([lm, mm]), rm), n_load


def _drive(eng, state, stream, *, chunk: int):
    """Feed ``stream`` through ``eng.tick_n`` in ``chunk``-tick calls.

    Returns ``(state, (rm_keys, rm_vals, rm_served) each [T, out],
    walls)`` with ``walls`` the seconds of each call, timed to
    ``block_until_ready`` (the first call includes its compile)."""
    import jax

    keys, vals, mask, rm = stream
    outs, walls = [], []
    for t0 in range(0, keys.shape[0], chunk):
        sl = slice(t0, t0 + chunk)
        t_start = time.perf_counter()
        state, res = eng.tick_n(state, keys[sl], vals[sl], mask[sl], rm[sl])
        jax.block_until_ready((state, res))
        walls.append(time.perf_counter() - t_start)
        outs.append(tuple(np.asarray(x) for x in
                          (res.rm_keys, res.rm_vals, res.rm_served)))
    return state, tuple(np.concatenate(x) for x in zip(*outs)), walls


def _served_multiset(rm_keys, rm_served):
    return np.sort(rm_keys[rm_served].astype(np.float64))


def _remaining(added, served):
    """Sorted multiset ``added - served``; raises if a served key was
    never added (the queue invented a key)."""
    ua, ca = np.unique(np.asarray(added, np.float64), return_counts=True)
    us, cs = np.unique(np.asarray(served, np.float64), return_counts=True)
    idx = np.searchsorted(ua, us)
    _check(bool(np.all(idx < ua.size)) and bool(np.all(ua[idx] == us))
           and bool(np.all(ca[idx] >= cs)),
           "served keys that were never added")
    ca = ca.copy()
    ca[idx] -= cs
    return np.repeat(ua, ca)


def _resident_keys(eng, state):
    keys, _, live = eng.resident(state)
    keys, live = np.asarray(keys), np.asarray(live)
    return np.sort(keys[live].astype(np.float64))


def _steady(walls, ticks_per_call: int):
    """Median seconds per tick over ``walls``; None when empty."""
    return float(np.median(walls)) / ticks_per_call if walls else None


def queue_phase(name: str, spec, *, resident: int = RESIDENT,
                mix_ticks: int = MIX_TICKS, chunk: int = CHUNK,
                p_add: float = P_ADD, seed: int = 0,
                clock: CompileClock) -> dict:
    """Load ``resident`` keys into ``make_engine(spec)``, run the DES
    mix, and check the served stream against the exact reference: rank
    error within the engine's envelope (0 for an exact engine), no key
    lost or invented, resident multiset equal to the reference's."""
    from repro.core.factory import make_engine
    from repro.quality.harness import replay

    eng = make_engine(spec)
    state = eng.init(seed=seed)
    rng = np.random.default_rng(seed)
    stream, n_load = _stream(eng.width, resident=resident,
                             mix_ticks=mix_ticks, chunk=chunk, p_add=p_add,
                             rng=rng)
    clock.lap()
    state, (rk, _, rs), walls = _drive(eng, state, stream, chunk=chunk)
    compile_s = clock.lap()
    keys, _, mask, rm = stream
    n_rm = int(rm[-1])
    q = replay(keys, mask, rk, rs, rm, record_from=n_load)
    added = keys[mask]
    served = _served_multiset(rk, rs)
    ref_left = _remaining(added, served)
    got_left = _resident_keys(eng, state)
    lost = int(added.size) - int(served.size) - int(got_left.size)
    envelope = int(eng.relax_bound(n_rm)) - n_rm
    n_load_calls = n_load // chunk
    _emit(name, compile_s=compile_s,
          first_call_s=walls[0],
          load_s_per_tick=_steady(walls[1:n_load_calls], chunk),
          mix_s_per_tick=_steady(walls[n_load_calls:], chunk),
          resident=int(got_left.size), served=int(served.size),
          rank_err_max=q["rank_err_max"], envelope=envelope, lost=lost)
    _check(lost == 0, f"{name}: {lost} keys lost")
    _check(q["rank_err_max"] <= envelope,
           f"{name}: rank_err_max {q['rank_err_max']} > envelope {envelope}")
    _check(np.array_equal(got_left, ref_left),
           f"{name}: resident multiset ({got_left.size} keys) differs from "
           f"the reference ({ref_left.size} keys)")
    _check(int(eng.size(state)) == ref_left.size,
           f"{name}: size() {int(eng.size(state))} != {ref_left.size}")
    return {"compile_s": compile_s, "rank_err_max": q["rank_err_max"],
            "resident": int(got_left.size)}


def _check_partition(rep: dict) -> None:
    total = rep["served"] + rep["shed"] + rep["expired"]
    if total != rep["arrivals"]:
        raise SmokeFailure(f"serve: served+shed+expired {total} != "
                           f"arrivals {rep['arrivals']}")
    if rep["in_flight"] or rep["retry_pending"]:
        raise SmokeFailure("serve: requests left in flight after the drain")


def serve_phase(*, n_ticks: int = SERVE_TICKS, warm_ticks: int = 10,
                seed: int = 0, clock: CompileClock) -> dict:
    """The elastic serving engine on a one-device mesh at rho 0.7: every
    arrival is served, shed or expired, exactly once.  A first
    ``run_sla`` of ``warm_ticks`` rounds compiles; the timed one runs
    ``n_ticks`` more on the same engine (its report is cumulative)."""
    from repro.serving.sla import build_engine, run_sla

    clock.lap()
    t0 = time.perf_counter()
    eng = build_engine(n_devices=1, rho=0.7, seed=seed)
    _check_partition(run_sla(eng, warm_ticks))
    warm_s = time.perf_counter() - t0
    compile_s = clock.lap()
    t0 = time.perf_counter()
    rep = run_sla(eng, n_ticks)
    wall = time.perf_counter() - t0
    rounds = rep["n_ticks"] + rep["drain_ticks"]
    _emit("serve", compile_s=compile_s, warm_s=warm_s,
          s_per_round=wall / max(rounds, 1), rounds=rounds,
          compiles_while_timed=clock.lap(),
          arrivals=rep["arrivals"], served=rep["served"], shed=rep["shed"],
          expired=rep["expired"], p99_ticks=rep["p99"])
    _check_partition(rep)
    if rep["served"] == 0:
        raise SmokeFailure("serve: nothing was served")
    return rep


def dist_phase(devices, *, width: int = 8192, lanes: int = 8,
               base=None, resident: int = RESIDENT,
               mix_ticks: int = MIX_TICKS, chunk: int = CHUNK,
               after_ticks: int = AFTER_TICKS, p_add: float = P_ADD,
               seed: int = 0, clock: CompileClock) -> dict:
    """``dist`` over ``devices`` against one-device ``sharded`` with the
    same lanes, fed one stream: the same served multiset every tick.
    Then ``remove_device`` of the last device conserves the resident
    multiset, and ``after_ticks`` ticks on the shrunk mesh conserve it
    and stay within the new envelope.

    Both engines are sized to lose one device (``spare_devices=1``, the
    elastic deployment; the reference gets the matching ``min_lanes``),
    so lane quotas cover a full-width batch on the shrunk mesh too."""
    import jax
    from jax.sharding import Mesh

    from benchmarks.pq_bench import mix_arrays
    from repro.core.config import PRODUCTION
    from repro.core.factory import EngineSpec, make_engine
    from repro.quality.harness import replay

    base = PRODUCTION if base is None else base
    d = len(devices)
    lpd = lanes // d
    mesh = Mesh(np.asarray(devices), ("data",))
    dist = make_engine(EngineSpec(engine="dist", width=width, base=base,
                                  lanes=lanes, n_devices=d,
                                  lanes_per_device=lpd, spare_devices=1),
                       mesh=mesh)
    ref = make_engine(EngineSpec(engine="sharded", width=width, base=base,
                                 lanes=lanes, min_lanes=lanes - lpd))
    _check(ref.cfg == dist.cfg.shard, "dist and sharded configs differ")
    rng = np.random.default_rng(seed)
    stream, n_load = _stream(width, resident=resident, mix_ticks=mix_ticks,
                             chunk=chunk, p_add=p_add, rng=rng)
    clock.lap()
    _, (sk, sv, ss), swalls = _drive(ref, ref.init(seed=seed), stream,
                                     chunk=chunk)
    ref_compile = clock.lap()
    dstate, (dk, dv, ds), dwalls = _drive(dist, dist.init(seed=seed),
                                          stream, chunk=chunk)
    dist_compile = clock.lap()
    # per-tick served multisets; unserved slots sort last
    big = np.iinfo(np.int32).max
    for what, a, b in (("keys", np.where(ds, dk, np.inf),
                        np.where(ss, sk, np.inf)),
                       ("vals", np.where(ds, dv, big),
                        np.where(ss, sv, big))):
        bad = np.flatnonzero(np.any(np.sort(a, axis=1) != np.sort(b, axis=1),
                                    axis=1))
        _check(bad.size == 0, f"dist: served {what} differ from sharded at "
               f"ticks {bad[:8].tolist()}")
    n_load_calls = n_load // chunk
    _emit("dist", devices=d, ref_compile_s=ref_compile,
          dist_compile_s=dist_compile,
          ref_mix_s_per_tick=_steady(swalls[n_load_calls:], chunk),
          dist_mix_s_per_tick=_steady(dwalls[n_load_calls:], chunk),
          ticks_compared=int(dk.shape[0]), served=int(ds.sum()))

    keys, _, mask, _ = stream
    before = _resident_keys(dist, dstate)
    _check(np.array_equal(before, _remaining(keys[mask],
                                             _served_multiset(dk, ds))),
           "dist: resident multiset differs from the reference")
    t0 = time.perf_counter()
    shrunk, dstate = dist.remove_device(dstate, d - 1)
    jax.block_until_ready(dstate)
    resize_s = time.perf_counter() - t0
    after = _resident_keys(shrunk, dstate)
    _check(np.array_equal(before, after),
           f"remove_device: resident multiset changed ({before.size} -> "
           f"{after.size} keys)")

    n_add = int(round(width * p_add))
    n_rm = width - n_add
    ak, av, am = mix_arrays(width, n_add, n_rm, after_ticks, rng, "uniform")
    rms = np.full(after_ticks, n_rm, np.int32)
    dstate, res = shrunk.tick_n(dstate, ak, av, am, rms)
    rk, rs = np.asarray(res.rm_keys), np.asarray(res.rm_served)
    q = replay(ak, am, rk, rs, rms, warm_keys=after)
    left = _remaining(np.concatenate([after, ak[am]]),
                      _served_multiset(rk, rs))
    got = _resident_keys(shrunk, dstate)
    envelope = int(shrunk.relax_bound(n_rm)) - n_rm
    _emit("dist_resize", devices_after=shrunk.cfg.n_devices,
          resident=int(after.size), resize_s=resize_s,
          after_ticks=after_ticks, served_after=int(rs.sum()),
          rank_err_max=q["rank_err_max"], envelope=envelope)
    _check(np.array_equal(got, left),
           "dist after resize: resident multiset differs from the reference")
    _check(q["rank_err_max"] <= envelope,
           f"dist after resize: rank_err_max {q['rank_err_max']} > "
           f"{envelope}")
    return {"devices_after": shrunk.cfg.n_devices,
            "rank_err_max": q["rank_err_max"]}


def _tpu_devices(chips: int):
    """The TPU devices, or exit non-zero: this script never falls back
    to another platform."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} but JAX sees {len(devs)}")
    print(f"# device kind={devs[0].device_kind} count={len(devs)}",
          flush=True)
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devs = _tpu_devices(args.chips)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    from repro.core.config import PRODUCTION
    from repro.core.factory import EngineSpec

    clock = CompileClock()
    if args.chips == 4:
        dist_phase(devs[:4], seed=args.seed, clock=clock)
    else:
        queue_phase("pqe", EngineSpec(engine="pqe", width=1024,
                                      base=PRODUCTION),
                    seed=args.seed, clock=clock)
        queue_phase("sharded", EngineSpec(engine="sharded", width=8192,
                                          lanes=8, base=PRODUCTION),
                    seed=args.seed, clock=clock)
        serve_phase(seed=args.seed, clock=clock)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
