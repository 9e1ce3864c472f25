"""Tiny cells for the CPU tests: the real mixes and metric readers,
with configurations small enough for a test run."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent

BASE = {"a_max": 64, "r_max": 64, "seq_cap": 512, "n_buckets": 16,
        "bucket_cap": 128, "detach_min": 4, "detach_max": 64,
        "detach_init": 8, "chop_patience": 8}

CONFIGS = {
    "tiny_exact": {"spec": {"engine": "pqe", "width": 64, "base": BASE},
                   "chips": 1, "resident": 600, "engine_seed": 0,
                   "guarantee": {"order": "exact", "rank_err_max": 0}},
    "tiny_relaxed": {"spec": {"engine": "sharded", "width": 128, "lanes": 8,
                              "base": BASE},
                     "chips": 1, "resident": 600, "engine_seed": 0,
                     "guarantee": {"order": "c-relaxed",
                                   "rank_err_max": 320}},
    "tiny_dist": {"spec": {"engine": "dist", "width": 128, "lanes": 8,
                           "n_devices": 4, "lanes_per_device": 2,
                           "spare_devices": 1, "base": BASE},
                  "chips": 4, "resident": 600, "engine_seed": 0,
                  "guarantee": {"order": "c-relaxed",
                                "rank_err_max": 416}},
}


#: readers kept for the relaxed cells that ``BENCHMARK.json`` holds back
HELD_BACK = [
    {"name": "rank_err_p99", "unit": "keys", "better": "lower",
     "source": "host_clock", "layer": "relaxation", "moves": "ops_per_s"},
    {"name": "collective_ms_per_tick", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "collectives",
     "moves": "ops_per_s"},
]


def bench_tree(dest: Path) -> dict:
    """A benchmark directory under ``dest`` with the real mixes and
    readers and the tiny configurations; returns its BENCHMARK dict
    (every metric, those of :data:`HELD_BACK` too, in every cell)."""
    dest = Path(dest)
    for sub in ("traffic", "metrics"):
        shutil.copytree(BENCH_DIR / sub, dest / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (dest / "configs").mkdir()
    for name, cfg in CONFIGS.items():
        (dest / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    with open(BENCH_DIR.parent / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["workloads"] = [
        {"name": f"{c}.{m}", "config": c, "traffic": m,
         "chips": CONFIGS[c]["chips"]}
        for c in CONFIGS for m in ("hold", "uniform")]
    known = {m["name"] for m in bench["per_layer"]}
    bench["per_layer"] += [dict(m) for m in HELD_BACK if m["name"] not in known]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    return bench
