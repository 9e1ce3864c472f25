"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (the assignment's format).

Figures map (DESIGN.md §10):
  Fig. 5  -> bench_fig5_mix50       (50/50 throughput vs batch width)
  Fig. 6  -> bench_fig6_mix80       (80/20 throughput vs batch width)
  Fig. 7  -> bench_fig7_add_breakdown
  Fig. 8  -> bench_fig8_rm_breakdown
  Table 1 -> bench_table1_headmoves
  Tables 2-3 (HTM) -> bench_tick_fusion (structural analogue, DESIGN §9)
  kernels -> bench_kernels (pallas-interpret vs jnp oracle wall time)
  dry-run -> bench_dryrun_summary (reads artifacts/dryrun JSONs)

CPU wall-times characterize *algorithmic* behavior (relative throughput
across designs, path breakdowns); they are never TPU numbers.  The dist
and serve cells run as child jax processes, so this harness runs on the
CPU only; chip_smoke.py is the path that runs on the chip.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

WIDTHS = (8, 16, 32, 64, 128)

#: per-impl quality record (rank error / staleness of the rep-0 run —
#: deterministic given the seed, so the min-of-reps timing and these
#: numbers describe the same stream) copied into BENCH_pq.json's
#: "quality" section; benchmarks/dist_bench.py emits the same shape
QUALITY_KEYS = ("rank_err_p50", "rank_err_p99", "rank_err_max",
                "stale_p50", "stale_p99", "stale_max",
                "n_served", "relax_bound", "rm_count", "lost")

#: rank_err_p99 budget of the tuner demo cell — roughly the w4096 L=8
#: envelope, i.e. "as relaxed as the widest engine we ship", so the
#: tuner's job is to CONFIRM the wide engine fits and the demo prices
#: what that budget buys over the strict exact baseline
TUNER_BUDGET = 4096.0


def _emit(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.2f},{derived}")


def bench_fig5_mix50() -> None:
    from benchmarks.pq_bench import IMPLS, bench_mix
    best = {}
    for impl in IMPLS:
        for w in WIDTHS:
            r = bench_mix(impl, w, 0.5, ticks=40)
            _emit(f"fig5_{impl}_w{w}", r["us_per_tick"],
                  f"{r['mops_per_s']:.3f}Mops/s")
            best[(impl, w)] = r["mops_per_s"]
    for w in WIDTHS[-2:]:
        ratio = best[("pqe", w)] / max(best[("fcskiplist", w)],
                                       best[("lfskiplist", w)])
        _emit(f"fig5_speedup_w{w}", 0.0, f"pqe_vs_best_other={ratio:.2f}x")


def bench_fig6_mix80() -> None:
    from benchmarks.pq_bench import IMPLS, bench_mix
    best = {}
    for impl in IMPLS:
        for w in WIDTHS:
            r = bench_mix(impl, w, 0.8, ticks=40)
            _emit(f"fig6_{impl}_w{w}", r["us_per_tick"],
                  f"{r['mops_per_s']:.3f}Mops/s")
            best[(impl, w)] = r["mops_per_s"]
    for w in WIDTHS[-2:]:
        ratio = best[("pqe", w)] / max(best[("fcskiplist", w)],
                                       best[("lfskiplist", w)])
        _emit(f"fig6_speedup_w{w}", 0.0, f"pqe_vs_best_other={ratio:.2f}x")


def bench_fig7_add_breakdown() -> None:
    from benchmarks.pq_bench import breakdown
    for dist in ("uniform", "des"):
        for pct in (80, 50, 20):
            b = breakdown(64, pct / 100.0, key_dist=dist)
            _emit(f"fig7_{dist}_add{pct}", b["us_per_tick"],
                  f"elim={b['add_eliminated']:.2f}"
                  f"|par={b['add_parallel']:.2f}"
                  f"|server={b['add_server']:.2f}")


def bench_fig8_rm_breakdown() -> None:
    from benchmarks.pq_bench import breakdown
    for dist in ("uniform", "des"):
        for pct in (80, 50, 20):
            b = breakdown(64, pct / 100.0, key_dist=dist)
            _emit(f"fig8_{dist}_add{pct}", b["us_per_tick"],
                  f"rm_elim={min(b['rm_eliminated'], 1.0):.2f}"
                  f"|rm_server={b['rm_server']:.2f}")


def bench_table1_headmoves() -> None:
    from benchmarks.pq_bench import breakdown
    for pct in (80, 50, 20):
        b = breakdown(64, pct / 100.0, ticks=120)
        _emit(f"table1_add{pct}", b["us_per_tick"],
              f"movehead%={100 * b['movehead_per_rm']:.2f}"
              f"|chophead%={100 * b['chophead_per_rm']:.2f}")


def bench_tick_fusion() -> None:
    """HTM analogue (DESIGN.md §9): the batch tick is a transaction that
    always commits; report ops committed per atomic tick vs. the paper's
    3.2-3.9 transactions *per op* under TSX."""
    from benchmarks.pq_bench import bench_mix
    for w in (16, 64):
        r = bench_mix("pqe", w, 0.5, ticks=40)
        _emit(f"htm_analogue_w{w}", r["us_per_tick"],
              f"ops_per_commit={2 * w}|aborts=0")


def bench_kernels() -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    rows, n = 4, 1024
    k = jnp.asarray(rng.uniform(0, 1e4, (rows, n)), jnp.float32)
    v = jnp.asarray(rng.integers(0, 1 << 20, (rows, n)), jnp.int32)
    f = jnp.zeros((rows, n), jnp.int32)

    pallas_bk = ops.resolve_backend("pallas_interpret")
    jnp_bk = ops.resolve_backend("jnp")
    for name, fn in (
        ("bitonic_pallas",
         lambda: ops.sort_kvf(k, v, f, backend=pallas_bk)),
        ("sort_jnp", lambda: ops.sort_kvf(k, v, f, backend=jnp_bk)),
    ):
        out = fn()
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(5):
            out = fn()
        jax.block_until_ready(out)
        _emit(f"kern_{name}_{rows}x{n}",
              (time.perf_counter() - t0) / 5 * 1e6, "sorted")

    a = jnp.sort(jnp.asarray(rng.uniform(0, 1e4, 1024), jnp.float32))
    b = jnp.sort(jnp.asarray(rng.uniform(0, 1e4, 256), jnp.float32))
    av = jnp.arange(1024, dtype=jnp.int32)
    bv = jnp.arange(256, dtype=jnp.int32)
    z1, z2 = jnp.zeros(1024, jnp.int32), jnp.zeros(256, jnp.int32)
    for name, be in (("merge_pallas", pallas_bk), ("merge_jnp", jnp_bk)):
        fn = lambda: ops.merge_sorted(a, av, z1, b, bv, z2, backend=be)  # noqa
        out = fn()
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(5):
            out = fn()
        jax.block_until_ready(out)
        _emit(f"kern_{name}_1024+256",
              (time.perf_counter() - t0) / 5 * 1e6, "merged")

    keys = jnp.asarray(rng.uniform(0, 1e4, 4096), jnp.float32)
    for name, be in (("radix_pallas", pallas_bk), ("select_jnp", jnp_bk)):
        fn = lambda: ops.select_threshold(keys, 256, backend=be)  # noqa
        out = fn()
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(5):
            out = fn()
        jax.block_until_ready(out)
        _emit(f"kern_{name}_4096", (time.perf_counter() - t0) / 5 * 1e6,
              "threshold")


def bench_dryrun_summary() -> None:
    """Per-cell roofline bound from the dry-run artifacts (§Roofline)."""
    d = Path("artifacts/dryrun")
    if not d.exists():
        _emit("dryrun_missing", 0.0, "run scripts/dryrun_sweep.py first")
        return
    for p in sorted(d.glob("*__16x16.json")):
        r = json.loads(p.read_text())
        if r.get("status") != "OK":
            _emit(f"dryrun_{p.stem}", 0.0, r.get("status", "?"))
            continue
        rl = r["roofline"]
        _emit(f"dryrun_{p.stem}", r["timing"]["compile_s"] * 1e6,
              f"bound={rl['bound_step_s']:.3f}s|dom={rl['dominant']}"
              f"|mfu={rl['mfu_bound']:.4f}"
              f"|fits={r['memory']['fits_hbm']}")


def _run_bench_child(script: str, csv_prefix: str, marker: str) -> dict:
    """Run ``script`` in a child process and return its ``marker`` JSON.

    The dist and serve benches force host device counts, which lock at
    the first jax init, so they cannot share this process.  A child that
    needs the chip cannot run beside a parent that holds it, so this
    refuses once the parent's jax is on an accelerator.  Any failure of
    the child raises: a run never drops cells silently."""
    import os
    import subprocess
    import sys

    import jax
    platform = jax.default_backend()
    if platform != "cpu":
        raise RuntimeError(
            f"{script} runs as a child jax process, but this process "
            f"already holds the {platform!r} backend; run.py's child "
            "benches are CPU-only (chip_smoke.py is the chip path)")
    env = {**os.environ,
           "PYTHONPATH": "src:" + os.environ.get("PYTHONPATH", ".")}
    proc = subprocess.run(
        [sys.executable, script],
        capture_output=True, text=True, timeout=2400, env=env)
    if proc.returncode != 0:
        raise RuntimeError(
            f"{script} failed (exit {proc.returncode}):\n"
            f"{proc.stderr[-4000:]}")
    for line in proc.stdout.strip().splitlines():
        if line.startswith(csv_prefix):
            print(line)
    for line in proc.stdout.splitlines():
        if line.startswith(marker + " "):
            return json.loads(line[len(marker) + 1:])
    raise RuntimeError(f"{script} produced no {marker} line")


def bench_dist_elimination() -> dict:
    """Elimination = communication avoidance (the paper's thesis at pod
    scale): the lanes-over-devices DistShardedQueue with pre-route
    elimination adaptive vs forced off, plus the single-device
    sharded_L8 reference — benchmarks/dist_bench.py, 8 forced host
    devices.  Returns its DIST_CELLS_JSON payload."""
    return _run_bench_child("benchmarks/dist_bench.py", "dist_",
                            "DIST_CELLS_JSON")


def bench_serve_sla() -> dict:
    """SLA cells of the overload-robust serving engine: steady /
    overload / bursty / chaos-kill regimes, time-to-serve quantiles in
    SIMULATED ticks (deterministic, so the gate sees latency-distribution
    drift, not runner noise) — benchmarks/serve_bench.py, 2 forced host
    devices.  Returns its SERVE_CELLS_JSON payload."""
    return _run_bench_child("benchmarks/serve_bench.py", "serve_",
                            "SERVE_CELLS_JSON")


def bench_straggler() -> None:
    from repro.ft.straggler import simulate
    r = simulate(n_items=64, n_workers=8, straggler=0, slow_factor=4.0)
    _emit("straggler_pq", r["pq"] * 1e6,
          f"speedup_vs_static={r['speedup']:.2f}x|ideal={r['ideal']:.2f}")


#: workload grid of the smoke bench: the single PR-2 cell (p_add=0.3,
#: "des") could not OBSERVE elimination wins — the paper's headline is
#: balanced mixes.  p_add sweeps under/at/over balance; key_dist pits
#: the elimination-friendly hold model ("des") against uniform keys.
SMOKE_GRID = tuple((p, d) for d in ("des", "uniform")
                   for p in (0.3, 0.5, 0.7))
SMOKE_GRID_WIDTH = 4096


def _grid_cell_name(width: int, p_add: float, key_dist: str) -> str:
    return f"w{width}_p{int(round(p_add * 100))}_{key_dist}"


def _tuner_demo(results: dict) -> dict:
    """Run the quality auto-tuner (repro.quality.tuner) on the grid's
    p_add=0.3 DES cell and price the tuned engine against the strict
    exact baseline (`pqe`) measured in the same process: the stated
    rank-error budget is spent on lanes, and the speedup it buys is the
    recorded, gated number (BENCH_pq.json quality.tuner_demo)."""
    from benchmarks.pq_bench import bench_mix
    from repro.quality.tuner import tune_lanes

    cname = _grid_cell_name(SMOKE_GRID_WIDTH, 0.3, "des")
    res = tune_lanes(width=SMOKE_GRID_WIDTH, p_add=0.3,
                     budget=TUNER_BUDGET, key_dist="des", lanes_max=8)
    tuned_us = min(
        bench_mix("sharded", SMOKE_GRID_WIDTH, 0.3, ticks=20,
                  key_dist="des", lanes=res.lanes,
                  settle=40)["us_per_tick"]
        for _ in range(3))
    strict_us = results[cname]["pqe"]
    return {
        "cell": cname,
        "metric": res.metric,
        "budget": TUNER_BUDGET,
        "lanes": res.lanes,
        "rank_err_p99": res.value,
        "strict_impl": "pqe",
        "strict_us": strict_us,
        "tuned_impl": f"sharded_L{res.lanes}",
        "tuned_us": round(tuned_us, 2),
        "speedup": round(strict_us / tuned_us, 2),
    }


def bench_smoke_json(out_path: str = "BENCH_pq.json",
                     merge_min: str = None) -> None:
    """CI perf-trajectory smoke: legacy width cells + a workload grid.

    Two cell families, each gated per cell by
    `scripts/check_bench_regression.py` (machine-normalized within the
    cell, never across cells):

    * legacy "w256"/"w4096" cells — the moveHead-heavy p_add=0.3 "des"
      mix over every impl incl. the sharded lane sweep (L ∈ {1,2,4,8}
      at w4096, {2,8} at w256); kept verbatim so the PR-over-PR
      trajectory stays diffable back to the seed;
    * the workload GRID at w4096 — p_add ∈ {0.3, 0.5, 0.7} ×
      key_dist ∈ {des, uniform} for `pqe`, `sharded_L8`,
      `sharded_L8_noelim` (pre-route elimination forced off), and
      `sharded_L8_adaptive` (the workload controller picking its own
      engine), so the balanced-mix elimination win — the paper's
      headline — AND the controller's regime-tracking are measured,
      regression-gated numbers instead of claims;
    * the MULTI-DEVICE cells (`*_dist`, benchmarks/dist_bench.py in a
      subprocess with 8 forced host devices) — `dist_sharded_D8` (the
      lanes-over-devices DistShardedQueue, D=8 × l=1), its
      elimination-off ablation, and the single-device `sharded_L8`
      reference measured in the SAME process, so the shard_map path's
      trajectory is gated per cell like the single-device grid;
    * the SERVING SLA cells (`serve_*`, benchmarks/serve_bench.py in a
      subprocess with 2 forced host devices) — time-to-serve
      p50/p99/p99.9 of the request engine under steady, overload,
      bursty, and chaos-kill regimes.  These quantiles are in SIMULATED
      clock ticks (deterministic given the seed), so they are exempt
      from the min-of-runs merge below and the gate on them catches
      real latency-distribution drift from policy/queue/fault-path
      edits, with widened per-quantile tolerances for the tails.

    Every grid and dist cell also gets a per-impl QUALITY record
    (rank_err_{p50,p99,max}, stale_{p50,p99,max}; DESIGN.md §12) in the
    payload's top-level "quality" section: the rep-0 served stream is
    replayed against the exact reference after the clock stops, and the
    regression gate asserts rank_err_max <= relax_bound - rm_count per
    cell — an ABSOLUTE, non-rebaselinable bound from the relaxation
    theorem, so a semantics regression cannot be waved through as a
    timing change.  The "tuner_demo" entry prices the quality budget:
    the auto-tuner's lane choice must beat the strict exact baseline by
    >= 1.2x at the stated budget.

    Each cell entry is the best of three runs: shared boxes showed up
    to 4x ambient inflation run-to-run, and the min is the standard
    noise-robust timing statistic.  ``merge_min`` (CLI: ``--merge-min
    PREV.json``) folds a previous result file in elementwise-min —
    this is how the COMMITTED baseline is built (several full smoke
    runs merged), since even min-of-3 single runs swing ~2x ambient;
    the stat field records "min_of_3_merged" so the provenance is
    visible.
    """
    from benchmarks.pq_bench import IMPLS, bench_mix
    results = {}
    for width in (256, 4096):
        cell = {}
        for impl in IMPLS:
            if impl == "sharded":
                lane_sweep = (1, 2, 4, 8) if width == 4096 else (2, 8)
                for lanes in lane_sweep:
                    us = min(
                        bench_mix(impl, width, 0.3, ticks=20,
                                  key_dist="des",
                                  lanes=lanes)["us_per_tick"]
                        for _ in range(3))
                    cell[f"sharded_L{lanes}"] = round(us, 2)
            else:
                us = min(
                    bench_mix(impl, width, 0.3, ticks=20,
                              key_dist="des")["us_per_tick"]
                    for _ in range(3))
                cell[impl] = round(us, 2)
        results[f"w{width}"] = cell
        for name, us in cell.items():
            _emit(f"smoke_{name}_w{width}", us, "us_per_tick")

    # column name -> (factory impl, bench_mix kwargs).  EVERY variant
    # settles 40 untimed ticks of the same stream so all columns enter
    # the clock with the same absorbed workload (at net-filling mixes a
    # settle-less impl would be measured on a much smaller queue —
    # apples to oranges).  For the adaptive column (the workload
    # controller, repro.core.adaptive) the settle is also its
    # measurement window: two decision windows (window=20, confirm=2)
    # to latch the cell's regime before the clock starts, exactly as a
    # long-running queue would have (the per-cell gate then holds it to
    # <=1.05x the cell's best FIXED engine; check_bench_regression.py).
    grid_variants = (
        ("pqe", "pqe", dict(settle=40)),
        ("sharded_L8", "sharded", dict(lanes=8, preroute="adaptive", settle=40)),
        ("sharded_L8_noelim", "sharded", dict(lanes=8, preroute="off", settle=40)),
        ("sharded_L8_adaptive", "adaptive",
         dict(lanes=8, preroute="adaptive", settle=40, window=20)),
    )
    hit_rates = {}
    quality = {}
    roofline = {}
    for p_add, key_dist in SMOKE_GRID:
        cname = _grid_cell_name(SMOKE_GRID_WIDTH, p_add, key_dist)
        # reps are INTERLEAVED across variants (rep-major, not
        # variant-major): the adaptive column is gated ABSOLUTELY
        # against the others in this cell, so every column must sample
        # the same ambient-noise windows — a variant-major loop runs
        # each column in a different thermal/load period and the
        # min-of-reps comparison inherits that drift
        runs = {name: [] for name, _, _ in grid_variants}
        for rep in range(4):
            for name, impl, kw in grid_variants:
                # roofline on every rep is near-free: the HLO analysis is
                # cached per variant (pq_bench._ROOFLINE_STATS), only the
                # rep's wall time is folded in — so the recorded record
                # below can come from the SAME run as the recorded time
                runs[name].append(bench_mix(impl, SMOKE_GRID_WIDTH, p_add,
                                            ticks=20, key_dist=key_dist,
                                            quality=rep == 0, roofline=True,
                                            **kw))
        cell = {}
        qcell = {}
        rcell = {}
        for name, _, _ in grid_variants:
            best = min(runs[name], key=lambda r: r["us_per_tick"])
            cell[name] = round(best["us_per_tick"], 2)
            qcell[name] = {k: runs[name][0][k] for k in QUALITY_KEYS}
            if "roofline" in best:
                rcell[name] = best["roofline"]
            if name == "sharded_L8":
                # hit rate from the SAME run the recorded time came from
                hit_rates[cname] = round(best["preroute_hit_per_tick"], 1)
        results[cname] = cell
        quality[cname] = qcell
        roofline[cname] = rcell
        for name, us in cell.items():
            _emit(f"smoke_{name}_{cname}", us, "us_per_tick")
        _emit(f"smoke_rank_err_{cname}", 0.0,
              "|".join(f"{n}={qcell[n]['rank_err_p99']}"
                       for n, _, _ in grid_variants))

    # quality auto-tuner demo (DESIGN.md §12): widen lanes until the
    # measured rank-error budget binds, then price the tuned engine
    # against the strict exact baseline measured in the SAME process
    # moments ago.  The regression gate holds speedup >= 1.2x
    # (--quality-spend-min): a stated budget must BUY something.
    tuner_demo = _tuner_demo(results)
    _emit(f"smoke_tuner_demo_{tuner_demo['cell']}", tuner_demo["tuned_us"],
          f"lanes={tuner_demo['lanes']}"
          f"|rank_err_p99={tuner_demo['rank_err_p99']}"
          f"<=budget={tuner_demo['budget']}"
          f"|speedup_vs_{tuner_demo['strict_impl']}="
          f"{tuner_demo['speedup']:.2f}x")

    # multi-device cells (subprocess, 8 forced host devices): the dist
    # engine vs the single-device reference on the same workload
    dist = bench_dist_elimination()
    dist_cells = dist["cells"]
    quality.update(dist.get("quality", {}))
    for cname, cell in dist_cells.items():
        results[cname] = cell
        for name, us in cell.items():
            _emit(f"smoke_{name}_{cname}", us, "us_per_tick")

    # serving SLA cells (subprocess, 2 forced host devices): quantiles
    # in simulated ticks
    serve = bench_serve_sla()
    serve_cells = serve["cells"]
    for cname, cell in serve_cells.items():
        results[cname] = cell
        for name, ticks in cell.items():
            _emit(f"smoke_{name}_{cname}", ticks, "time_to_serve_ticks")

    payload = {
        "workload": {
            "legacy_cells": {"p_add": 0.3, "key_dist": "des"},
            "grid": {"width": SMOKE_GRID_WIDTH,
                     "p_add": [0.3, 0.5, 0.7],
                     "key_dist": ["des", "uniform"],
                     "impls": [n for n, _, _ in grid_variants],
                     "adaptive_settle_ticks": 24},
            # straight from the dist bench's own payload — the cell
            # definition has one source of truth (dist_bench.CELLS)
            "dist_cells": dist["meta"],
            # likewise from serve_bench.CELLS; its metric field marks
            # the serve_* cells as simulated-tick quantiles, not µs
            "serve_cells": serve["meta"],
            "ticks": 20, "metric": "us_per_tick", "stat": "min_of_3",
            "driver": "tick_n_scan_for_pqe_and_sharded"},
        # trajectory anchors: seed/PR-1/PR-2 numbers on the p_add=0.3
        # "des" w4096 cell (each measured on its own PR's machine; the
        # regression gate compares machine-normalized shares, not these
        # absolute values)
        "seed_reference": {"pqe_w4096": 21395.0,
                           "pqe_w4096_paired_new": 7805.5,
                           "paired_speedup": 2.74,
                           "pr1_pqe_w4096": 6470.69,
                           "pr1_sharded_L8_w4096": 20521.21,
                           "pr2_pqe_w4096": 3447.88,
                           "pr2_sharded_L8_w4096": 1838.31},
        "preroute_hit_per_tick": hit_rates,
        # rank-error / staleness observability (DESIGN.md §12): per-cell
        # per-impl records from the rep-0 runs, kept OUTSIDE "results"
        # so the timing gate's per-cell geomean normalization never
        # ingests a quality number.  Always fresh: merge_min below does
        # not touch this section (rank errors are deterministic given
        # the seed, and the tuner demo's strict/tuned timings are a
        # same-process pair that min-merging would split across runs).
        "quality": {**quality, "tuner_demo": tuner_demo},
        # roofline observability (DESIGN.md §13): per-cell per-impl
        # achieved-vs-peak records from the SAME run each recorded time
        # came from (repro.roofline.measure vs the TPU v5e reference
        # roof; "device" records where the bench actually ran).  Kept
        # OUTSIDE "results" like "quality" so the timing gate never
        # ingests one, and deliberately NOT min-merged: the record must
        # stay paired with this run's machine and wall time.
        "roofline": roofline,
        "results": results,
    }
    if merge_min:
        prev_all = json.loads(Path(merge_min).read_text())
        prev = prev_all["results"]
        prev_hits = prev_all.get("preroute_hit_per_tick", {})
        for cname, cell in payload["results"].items():
            if cname in serve_cells:
                # serve quantiles are deterministic simulated ticks —
                # min-merging them with a pre-change run would splice
                # two different latency distributions
                continue
            for impl in cell:
                pv = prev.get(cname, {}).get(impl, float("inf"))
                if pv < cell[impl]:
                    cell[impl] = round(pv, 2)
                    # keep the hit rate paired with the run whose time
                    # is being recorded
                    if impl == "sharded_L8" and cname in prev_hits:
                        payload["preroute_hit_per_tick"][cname] = (
                            prev_hits[cname])
        payload["workload"]["stat"] = "min_of_3_merged"
    # the headline elimination-win ratios are computed AFTER any merge,
    # from exactly the values being written — the log must never quote
    # a ratio the committed artifact does not support
    for p_add, key_dist in SMOKE_GRID:
        cname = _grid_cell_name(SMOKE_GRID_WIDTH, p_add, key_dist)
        cell = payload["results"][cname]
        _emit(f"smoke_elim_win_{cname}", 0.0,
              f"noelim/elim="
              f"{cell['sharded_L8_noelim'] / cell['sharded_L8']:.2f}x"
              f"|hit_per_tick={payload['preroute_hit_per_tick'][cname]}")
    for cname in dist_cells:
        cell = payload["results"][cname]
        # not every dist cell carries every impl (the degraded cell
        # pairs healthy/throttled only) — emit the ratios present
        d8 = cell["dist_sharded_D8"]
        parts = []
        if "sharded_L8" in cell:
            parts.append(f"dist_D8/local_L8={d8 / cell['sharded_L8']:.2f}x")
        if "dist_sharded_D8_noelim" in cell:
            parts.append(
                f"elim_win={cell['dist_sharded_D8_noelim'] / d8:.2f}x")
        if "dist_sharded_D8_degraded" in cell:
            parts.append(
                f"degraded/healthy="
                f"{cell['dist_sharded_D8_degraded'] / d8:.2f}x")
        _emit(f"smoke_dist_overhead_{cname}", 0.0, "|".join(parts))
    Path(out_path).write_text(json.dumps(payload, indent=2) + "\n")
    print(f"# wrote {out_path}")


def main() -> None:
    import sys
    print("name,us_per_call,derived")
    if "--smoke" in sys.argv:
        out = "BENCH_pq.json"
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        merge = None
        if "--merge-min" in sys.argv:
            merge = sys.argv[sys.argv.index("--merge-min") + 1]
        bench_smoke_json(out, merge_min=merge)
        return
    bench_fig5_mix50()
    bench_fig6_mix80()
    bench_fig7_add_breakdown()
    bench_fig8_rm_breakdown()
    bench_table1_headmoves()
    bench_tick_fusion()
    bench_kernels()
    bench_straggler()
    bench_dist_elimination()
    bench_serve_sla()
    bench_dryrun_summary()
    bench_smoke_json()


if __name__ == "__main__":
    main()
