"""Run one benchmark cell once on the TPU chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The run
exits non-zero, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for.  Its last line on standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown`` of the profiler trace,
and last ``checks``, each number compared beside its limit.  The same
checks are the last lines on standard error.

JAX's persistent compilation cache is kept in ``.jax_cache`` at the
root of the checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names
another directory.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def tpu_devices(chips: int):
    """The TPU devices, or exit non-zero: no fallback to another
    platform."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        sys.exit(f"bench: the cell asks for {chips} chips; JAX sees "
                 f"{len(devs)}")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    bench = harness.load_benchmark(ROOT)
    cell = harness.cell_of(bench, args.workload)
    devices = tpu_devices(int(cell["chips"]))
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    # every program of the run, small ones too, is found there next time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    result = harness.run_cell(
        bench, args.workload, seed=args.seed, seconds=args.seconds,
        traced=bool(args.trace), devices=devices, t_start=T_START,
        emit=lambda s: print(s, flush=True))
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
