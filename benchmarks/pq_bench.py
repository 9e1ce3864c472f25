"""Shared machinery for the priority-queue benchmarks (paper §4).

The paper's benchmark: threads flip a p-coin between add() and
removeMin(); the structure is pre-warmed with 2000 elements; throughput is
ops/s.  The batch-world analogue maps *thread count* to *op-batch width*
per tick: a width-W tick carries the work W threads would submit
concurrently.

Every implementation is resolved through the unified factory
(repro.core.factory) and driven through the QueueEngine protocol, so one
driver measures all of them — including the adaptive workload controller
(impl="adaptive"), which picks its own engine per regime.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import PQConfig
from repro.core.factory import EngineSpec, make_engine

WARM_ELEMENTS = 2000     # paper: "inserting 2000 elements ... stable state"
KEY_HI = 100_000.0

#: lane count for the "sharded" impl when the caller does not pick one
DEFAULT_LANES = 4

#: impl names the full-figure benches sweep (run.py figs 5-6 iterate this)
IMPLS = ("pqe", "fcskiplist", "lfskiplist", "sharded")

#: engine kinds with a lax.scan tick_n driver (one dispatch per measured
#: run; amortizes per-tick dispatch, a measurable slice at ms-scale ticks)
SCAN_KINDS = ("pqe", "sharded", "adaptive")


def make_cfg(width: int) -> PQConfig:
    return PQConfig(
        a_max=width, r_max=width,
        seq_cap=max(4096, 4 * width),
        n_buckets=64, bucket_cap=max(64, WARM_ELEMENTS // 16),
        detach_min=8, detach_max=65536, detach_init=256,
        halve_threshold=1000, double_threshold=100)


def make_impl_engine(impl: str, width: int, *, lanes: int = DEFAULT_LANES,
                     preroute: str = "adaptive", min_lanes: int = None,
                     window: int = None, backend=None):
    """Resolve one bench impl to its engine via the unified factory.

    `lanes`/`preroute`/`min_lanes` only affect the lane-based engines
    (sharded / adaptive); `preroute` selects the sharded queue's
    pre-route elimination gate (adaptive|on|off) — the bench grid
    measures "off" as the disabled comparison point.  `window` sets the
    adaptive controller's decision cadence in ticks (its deployment
    knob: decisions per window cost one host round-trip).  `backend`
    is the spec-level kernel backend (jnp | pallas | pallas_interpret |
    auto); None keeps the base config's backend.
    """
    controller = None
    if window is not None:
        from repro.core.adaptive import ControllerConfig
        controller = ControllerConfig(window=window)
    return make_engine(EngineSpec(
        engine=impl, width=width, base=make_cfg(width), lanes=lanes,
        min_lanes=min_lanes, preroute=preroute, controller=controller,
        backend=backend))


def mix_arrays(width: int, n_add: int, n_rm: int, ticks: int, rng,
               key_dist: str, *, resident: int = WARM_ELEMENTS):
    """The p-coin mix workload as host arrays: ``(keys [T, W] f32,
    vals [T, W] i32, mask [T, W] bool)``, the first ``n_add`` slots of
    each tick live.

    key_dist "des" is the hold model: a virtual clock advances by the
    key span that ``n_rm`` removals consume from ``resident`` keys
    spread over [0, KEY_HI), and new keys land an exponential increment
    above it (clustered just above the current minimum).  "uniform"
    draws over the whole key space.
    """
    ak = np.full((ticks, width), np.inf, np.float32)
    av = np.broadcast_to(np.arange(width, dtype=np.int32),
                         (ticks, width)).copy()
    mask = np.zeros((ticks, width), bool)
    mask[:, :n_add] = True
    step = KEY_HI / max(resident, 1)
    lo = 0.0
    for t in range(ticks):
        if key_dist == "des":
            lo += n_rm * step
            ak[t, :n_add] = lo + rng.exponential(step * 8, n_add)
        else:
            ak[t, :n_add] = rng.uniform(0, KEY_HI, n_add)
    return ak, av, mask


def gen_mix_batches(width: int, n_add: int, n_rm: int, ticks: int, rng,
                    key_dist: str):
    """Pre-generated per-tick op batches of the p-coin mix workload
    (host work out of every timed loop) at the paper's warm depth.
    SHARED by bench_mix and benchmarks/dist_bench.py: the dist cells are
    only comparable to their in-process single-device reference because
    both drivers consume bit-identical streams from this one generator
    (:func:`mix_arrays` documents the key distributions).
    """
    ak, av, mask = mix_arrays(width, n_add, n_rm, ticks, rng, key_dist)
    return [(jnp.asarray(k), jnp.asarray(v), jnp.asarray(m))
            for k, v, m in zip(ak, av, mask)]


def _warm(eng, rng):
    """Pre-warm to the paper's 2000-element stable state.  Returns
    (state, warm_keys): the keys are the quality replay's initial
    resident multiset (zero-remove ticks serve nothing and the router
    drops nothing at slack 1.0, so everything inserted is resident)."""
    state = eng.init(seed=0)
    w = eng.width
    keys = rng.uniform(0, KEY_HI, WARM_ELEMENTS).astype(np.float32)
    for i in range(0, WARM_ELEMENTS, w):
        chunk = keys[i:i + w]
        ak = np.full((w,), np.inf, np.float32)
        av = np.zeros((w,), np.int32)
        mask = np.zeros((w,), bool)
        ak[:len(chunk)] = chunk
        mask[:len(chunk)] = True
        state, _ = eng.tick(state, jnp.asarray(ak), jnp.asarray(av),
                            jnp.asarray(mask), jnp.asarray(0))
    return state, keys


def _stack(batches):
    return (jnp.stack([b[0] for b in batches]),
            jnp.stack([b[1] for b in batches]),
            jnp.stack([b[2] for b in batches]))


# variant-key -> HloStats for bench_mix(roofline=True); see capture site.
_ROOFLINE_STATS = {}


def bench_mix(impl: str, width: int, p_add: float, *, ticks: int = 50,
              seed: int = 0, key_dist: str = "uniform",
              lanes: int = DEFAULT_LANES, preroute: str = "adaptive",
              min_lanes: int = None, settle: int = 0,
              window: int = None, scan: bool = True,
              quality: bool = False,
              roofline: bool = False, backend=None) -> Dict[str, float]:
    """Throughput of one implementation at one width and add-fraction.

    key_dist:
      * "uniform" — keys uniform over the whole space (worst case for
        elimination: a fresh add rarely beats the queue minimum);
      * "des" — discrete-event-simulation style ("hold model"): new keys
        cluster just above the current minimum, the paper's motivating
        scheduler workload, where elimination thrives.

    `settle` prepends that many UNTIMED ticks of the same mix stream
    (one continuous generator draw, so the DES frontier keeps drifting):
    the adaptive controller's measurement window — it must latch its
    regime before the clock starts, exactly as a long-running queue
    would have.  `scan=True` drives engines with a scan tick_n
    (SCAN_KINDS) in one dispatch; others fall back to the eager loop.

    `quality=True` additionally replays the run's served stream against
    the exact reference (repro.quality.harness) and adds the rank-error
    / staleness fields (rank_err_{p50,p99,max}, stale_{p50,p99,max},
    relax_bound, rm_count, lost) to the result.  ``lost`` counts keys
    the engine silently shed (capacity overflow on net-filling mixes);
    nonzero means the replay's no-drop assumption is broken and the
    record is exempt from the envelope gate.  The replay happens AFTER the
    clock stops, on the results the timed run already materializes —
    settle ticks feed the reference without entering the aggregates, so
    the quality window and the timing window coincide.

    `roofline=True` (scan path only) additionally compiles the exact
    timed `tick_n` program, analyzes its optimized HLO, and attaches an
    achieved-vs-peak record (repro.roofline.measure) under
    out["roofline"] — flops / HBM-proxy bytes vs the TPU v5e reference
    roof, with the actual runtime device recorded honestly.

    Returns {us_per_tick, mops_per_s, ...stats}.
    """
    eng = make_impl_engine(impl, width, lanes=lanes, preroute=preroute,
                           min_lanes=min_lanes, window=window,
                           backend=backend)
    rng = np.random.default_rng(seed)
    state, warm_keys = _warm(eng, rng)

    if eng.kind == "adaptive" and settle:
        # re-phase the decision windows to the measured stream (warm
        # ticks must not shift a window boundary into the timed region),
        # then snap settle so the TIMED run starts window-aligned: the
        # timed ticks execute as whole decision windows, no
        # partial-chunk dispatches inside the clock.  With settle a
        # multiple of the window the snap is a no-op, so the adaptive
        # engine consumes the SAME settle+timed stream ticks as the
        # fixed impls it is gated against.
        state = dataclasses.replace(state, tick_count=0)
        settle += -settle % eng.ctl_cfg.window

    n_add = int(round(width * p_add))
    n_rm = width - n_add
    batches = gen_mix_batches(eng.width, n_add, n_rm, settle + ticks, rng,
                              key_dist)
    settle_b, timed_b = batches[:settle], batches[settle:]
    rmc = jnp.asarray(n_rm, jnp.int32)

    use_scan = scan and eng.kind in SCAN_KINDS
    q_res = []            # per-segment (rm_keys [t, out_w], rm_served)
    if settle_b:
        if use_scan:
            sk, sv, sm = _stack(settle_b)
            state, sres = eng.tick_n(state, sk, sv, sm,
                                     jnp.full((settle,), n_rm, jnp.int32))
            if quality:
                q_res.append((np.asarray(sres.rm_keys),
                              np.asarray(sres.rm_served)))
        else:
            for b in settle_b:
                state, sres = eng.tick(state, *b, rmc)
                if quality:
                    q_res.append((np.asarray(sres.rm_keys)[None],
                                  np.asarray(sres.rm_served)[None]))
        jax.block_until_ready(state)

    # the donating ticks consume their state argument: warm up / compile
    # on a throwaway copy so the measured run starts from the warm state.
    # For the adaptive engine the spare run replays the EXACT decision
    # sequence the timed run will take (same stream, same controller
    # state), so every kernel and switch path it needs is compiled.
    spare = jax.tree.map(jnp.copy, state)
    if use_scan:
        stak, stav, stam = _stack(timed_b)
        rms = jnp.full((ticks,), n_rm, jnp.int32)
        s2, _ = eng.tick_n(spare, stak, stav, stam, rms)
        jax.block_until_ready(s2)
        t0 = time.perf_counter()
        state, res = eng.tick_n(state, stak, stav, stam, rms)
        jax.block_until_ready(state)
        dt = time.perf_counter() - t0
    else:
        s2, _ = eng.tick(spare, *timed_b[0], rmc)
        jax.block_until_ready(s2)
        timed_res = []
        t0 = time.perf_counter()
        for t in range(ticks):
            state, res = eng.tick(state, *timed_b[t], rmc)
            if quality:
                timed_res.append(res)
        jax.block_until_ready(state)
        dt = time.perf_counter() - t0
        if quality:
            for r in timed_res:
                q_res.append((np.asarray(r.rm_keys)[None],
                              np.asarray(r.rm_served)[None]))

    out = {
        "us_per_tick": dt / ticks * 1e6,
        "mops_per_s": width * ticks / dt / 1e6,
    }
    if roofline and use_scan and eng.kind != "adaptive":
        # achieved-vs-peak record for this cell's timed run.  The scanned
        # tick program only depends on shapes and engine config — not on
        # p_add/key_dist — so the (expensive) HLO analysis is cached per
        # variant and only the wall time is folded in per cell.  Lowering
        # reads avals only (post-run state is fine, donation never fires).
        # The adaptive engine is excluded: its tick_n is a HOST-side
        # chunk driver (one host pull per decision window, DESIGN.md
        # §11), not a single jit program — there is no one compiled
        # module whose flop/byte counts describe the run.
        from repro.roofline import measure
        from repro.roofline.hlo_stats import analyze
        vkey = (impl, width, lanes, preroute, min_lanes, window, ticks,
                backend)
        st = _ROOFLINE_STATS.get(vkey)
        if st is None:
            st = analyze(measure.compiled_text_of(
                eng.tick_n, state, stak, stav, stam, rms))
            _ROOFLINE_STATS[vkey] = st
        out["roofline"] = measure.record_from_stats(st, dt, n_ticks=ticks)
    if quality:
        if use_scan:
            q_res.append((np.asarray(res.rm_keys),
                          np.asarray(res.rm_served)))
        from repro.quality.harness import replay
        out.update(replay(
            np.stack([np.asarray(b[0]) for b in batches]),
            np.stack([np.asarray(b[2]) for b in batches]),
            np.concatenate([k for k, _ in q_res]),
            np.concatenate([s for _, s in q_res]),
            np.full((len(batches),), n_rm, np.int64),
            warm_keys=warm_keys, record_from=settle))
        out["relax_bound"] = int(eng.relax_bound(n_rm))
        out["rm_count"] = int(n_rm)
        # conservation audit: the replay assumes the engine drops
        # nothing, but a net-filling mix (n_add > n_rm) eventually
        # overflows the finite structure and keys are silently shed.
        # Shed keys sit in the meter's union as phantoms and, on DES
        # streams (drops cluster at the serve frontier), inflate every
        # later rank — so lossy records are measured-but-exempt in the
        # regression gate (scripts/check_bench_regression.py).
        _, _, live = eng.resident(state)
        n_in = int(warm_keys.size) + sum(
            int(np.asarray(b[2]).sum()) for b in batches)
        n_out = sum(int(s.sum()) for _, s in q_res)
        out["lost"] = n_in - n_out - int(np.asarray(live).sum())
    kind = eng.kind
    if kind == "adaptive":
        for k, v in eng.controller_stats(state).items():
            if isinstance(v, (int, float)):
                out[f"ctl_{k}"] = v
        out["ctl_engine_is_pqe"] = int(state.kind == "pqe")
        kind = state.kind          # inner stats of whatever it landed on
        s = eng.stats(state)
    else:
        s = eng.stats(state)
    if kind == "pqe":
        for k in ("add_imm_elim", "add_upc_elim", "add_seq", "add_par",
                  "rm_seq", "rm_par", "rm_empty", "n_movehead",
                  "n_chophead", "n_removes"):
            out[k] = int(getattr(s, k))
    elif kind == "sharded":
        out["preroute_elim"] = int(s.n_preroute_elim)
        out["preroute_ticks"] = int(s.n_preroute_ticks)
        out["preroute_hit_per_tick"] = (int(s.n_preroute_elim)
                                        / max(int(s.n_ticks), 1))
        out["elim_ema"] = float(s.elim_ema)
        out["balance_ema"] = float(s.balance_ema)
        out["lane_add_elim"] = int(s.lane.add_imm_elim
                                   + s.lane.add_upc_elim)
        out["lane_rm_served"] = int(s.lane.rm_seq + s.lane.rm_par)
    return out


def breakdown(width: int, p_add: float, *, ticks: int = 80,
              seed: int = 0, key_dist: str = "uniform") -> Dict[str, float]:
    """Figs. 7–8: fraction of adds/removes served by each path."""
    r = bench_mix("pqe", width, p_add, ticks=ticks, seed=seed,
                  key_dist=key_dist)
    adds = r["add_imm_elim"] + r["add_upc_elim"] + r["add_seq"] + r["add_par"]
    rms = max(r["n_removes"], 1)
    elim = r["add_imm_elim"] + r["add_upc_elim"]
    return {
        "add_eliminated": elim / max(adds, 1),
        "add_parallel": r["add_par"] / max(adds, 1),
        "add_server": r["add_seq"] / max(adds, 1),
        "rm_eliminated": elim / rms,
        "rm_server": (r["rm_seq"] + r["rm_par"]) / rms,
        "movehead_per_rm": r["n_movehead"] / rms,
        "chophead_per_rm": r["n_chophead"] / rms,
        "us_per_tick": r["us_per_tick"],
    }
