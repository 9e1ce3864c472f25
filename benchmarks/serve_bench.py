"""SLA cells for the serving engine (subprocess, 2 forced host devices).

Four cells, one per regime the overload-robust engine must hold
(ISSUE 7 acceptance): steady state (rho 0.7), sustained overload
(rho 1.5, >= 500 ticks — depth must stay bounded by the admission cap
and every request must land in exactly one outcome class), a bursty
MMPP stream, and chaos (one seeded device kill mid-serving — zero lost
or duplicated requests, re-shard instead of wedge).  Each cell records
time-to-serve p50 / p99 / p99.9 of the served class.

The quantiles are measured in SIMULATED CLOCK TICKS, not wall time:
given the seed they are deterministic and machine-independent, so the
committed BENCH_pq.json numbers reproduce exactly anywhere — what the
regression gate catches is REAL latency-distribution drift from code
changes (policy, queue, or fault-path edits), not runner noise.  The
tail cells still get quantile-aware tolerances from
scripts/check_bench_regression.py because legitimate policy changes
move p99/p99.9 much more than p50.

Every run also re-asserts the hard robustness invariants (wedge-free
overload, exact partition, conservation across the kill) — a bench that
records numbers from a broken run would gate garbage.

Emits ``serve_<cell>,...`` CSV lines plus one machine-readable
``SERVE_CELLS_JSON {...}`` line that benchmarks/run.py --smoke folds
into BENCH_pq.json as ``serve_*`` cells.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import json  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402

N_DEVICES = 2
SEED = 0
DEPTH_CAP = 48
N_SLOTS = 8

#: cell -> build_engine/run kwargs (single source of truth; run.py
#: copies this whole mapping into BENCH_pq.json's workload metadata)
CELLS = {
    "serve_steady": dict(rho=0.7, pattern="poisson", ticks=300),
    "serve_over": dict(rho=1.5, pattern="poisson", ticks=500),
    "serve_burst": dict(rho=1.0, pattern="bursty", ticks=300,
                        burst_factor=4.0),
    "serve_chaos": dict(rho=0.9, pattern="poisson", ticks=120,
                        chaos="kill:1@10", spare_devices=1),
    # the quality-relaxed mode (DESIGN.md §12): same workload as
    # serve_steady, but deadline slack is spent on deferred, coalesced
    # serve rounds — cheaper ticks when SLAs permit.  The run asserts
    # the staleness budget held (defer runs <= max_defer) and that the
    # mode actually skipped rounds; the recorded quantiles price the
    # deferral in simulated ticks next to the strict twin.
    "serve_relaxed": dict(rho=0.7, pattern="poisson", ticks=300,
                          quality=dict(max_defer=3, defer_frac=0.5)),
}


def run_cell(name: str) -> dict:
    from repro.ft.inject import parse_chaos
    from repro.serving import build_engine, run_sla

    spec = dict(CELLS[name])
    ticks = spec.pop("ticks")
    chaos = spec.pop("chaos", None)
    schedule = (parse_chaos(chaos, n_devices=N_DEVICES)
                if chaos else None)
    eng = build_engine(
        n_devices=N_DEVICES, lanes_per_device=2, width=64,
        n_slots=N_SLOTS, seed=SEED, schedule=schedule,
        depth_cap=DEPTH_CAP, **spec)
    rep = run_sla(eng, ticks)

    # robustness invariants re-asserted on the measured run itself
    assert rep["max_depth"] <= DEPTH_CAP, (
        f"{name}: depth {rep['max_depth']} escaped the admission cap")
    assert rep["served"] + rep["shed"] + rep["expired"] == rep["arrivals"], (
        f"{name}: outcome partition broken")
    assert rep["in_flight"] == 0 and rep["retry_pending"] == 0
    if schedule is not None:
        assert len(eng.queue.live) == N_DEVICES - 1, (
            f"{name}: scheduled kill never fired")
    if name == "serve_over":
        assert rep["shed"] > 0, "overload cell did not shed — not overload"
    if "quality" in CELLS[name]:
        budget = CELLS[name]["quality"]["max_defer"]
        assert rep["max_defer_run"] <= budget, (
            f"{name}: defer run {rep['max_defer_run']} broke the "
            f"staleness budget {budget}")
        assert rep["deferred_ticks"] > 0, (
            f"{name}: quality-relaxed mode never deferred a round — "
            "the cell is not exercising the mode")
    return rep


def main() -> None:
    ndev = len(jax.devices())
    assert ndev == N_DEVICES, (
        f"host device count is {ndev}, wanted {N_DEVICES} — "
        "--xla_force_host_platform_device_count not honored")
    cells = {}
    for name in CELLS:
        rep = run_cell(name)
        cells[name] = {
            "p50": round(rep["p50"], 2),
            "p99": round(rep["p99"], 2),
            "p999": round(rep["p999"], 2),
        }
        served_frac = rep["served"] / max(rep["arrivals"], 1)
        extra = ""
        if "quality" in CELLS[name]:
            extra = (f"|deferred={rep['deferred_ticks']}"
                     f"|max_defer_run={rep['max_defer_run']}"
                     f"|coalesced={rep['coalesced_serves']}")
        print(f"{name},{cells[name]['p99']:.2f},"
              f"p50={cells[name]['p50']}|p999={cells[name]['p999']}"
              f"|served={served_frac:.2f}|shed={rep['shed']}"
              f"|expired={rep['expired']}|max_depth={rep['max_depth']}"
              f"{extra}")
    payload = {
        "meta": {
            "devices": N_DEVICES,
            "depth_cap": DEPTH_CAP,
            "n_slots": N_SLOTS,
            "seed": SEED,
            "cells": {k: {kk: vv for kk, vv in v.items()}
                      for k, v in CELLS.items()},
            "metric": "time_to_serve_sim_ticks",
            "stat": "deterministic_single_run",
            "runner": "benchmarks/serve_bench.py subprocess, forced host "
                      "devices",
        },
        "cells": cells,
    }
    print("SERVE_CELLS_JSON " + json.dumps(payload))


if __name__ == "__main__":
    main()
