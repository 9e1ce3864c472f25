"""One traced run of a cell, reduced by the program's scopes and the
dispatching side's host events.

    python3 bench/scoped_run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace-dir <dir> [--out <file.json>] [--tiny] [--as-text]

The run is ``bench/run.py``'s with ``--trace 1``, with two differences
that :mod:`bench.scopes` needs: the profile holds the executed modules'
HLO (``ProfileOptions.enable_hlo_proto``), and each garbage collection
in the run is a ``gc`` span (``repro.core.obs.gc_spans``).  The result
line holds the cell's end-to-end metrics beside its per-layer ones, so
that a traced run's ``ops_per_s`` can be set against an untraced one's;
``scoped`` holds the scope breakdown, ``idle_by_host``, the five
per-tick metrics of :meth:`bench.scopes.ScopedSummary.per_tick_ms`,
the largest ops with their scopes, the idle time inside programs,
the seconds ``stop_trace`` took, and, with ``--as-text`` in a ``pqe``
cell, how far the op names and scopes of the tick program compiled
again (``as_text()``) agree with those of the module the profile
holds.  The trace stays in ``--trace-dir``.  ``--tiny`` runs the tiny
cells of :mod:`bench.tiny` in place of ``BENCHMARK.json``'s.  It exits
non-zero where JAX finds no TPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _profiled_with_hlo(stop_s: list):
    """``harness._profiled`` with the modules' HLO kept in the profile;
    appends the seconds ``stop_trace`` took to ``stop_s``."""
    import jax

    @contextlib.contextmanager
    def profiled(log_dir):
        if log_dir is None:
            yield
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = True
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            yield
        finally:
            t = time.perf_counter()
            jax.profiler.stop_trace()
            stop_s.append(time.perf_counter() - t)

    return profiled


def _as_text_matches(engine, xplane, op_s) -> dict:
    """Op names and scopes of the pqe tick program, compiled again for
    the same argument shapes, against the executed module in the
    profile: the instructions of each, those whose name and scope
    agree, and the share of the trace's op time (``op_s``, by name) on
    names whose scope agrees."""
    import jax
    import numpy as np

    from bench import scopes
    from repro.core import pqueue

    w = engine.width
    state = jax.eval_shape(engine.init)
    args = (np.zeros((1, w), np.float32), np.zeros((1, w), np.int32),
            np.zeros((1, w), bool), np.zeros((1,), np.int32))
    text = pqueue.tick_n.lower(engine.cfg, state, *args).compile().as_text()
    compiled = scopes.op_scopes(text)
    profiled = [scopes.op_scopes(t)
                for name, t in scopes.hlo_modules(xplane).items()
                if name.startswith("jit_tick_n")]
    if not profiled:
        return {"modules": 0}
    ran = profiled[0]
    same = {n for n, s in ran.items() if compiled.get(n) == s}
    total = sum(op_s.values())
    return {"modules": len(profiled), "profiled": len(ran),
            "compiled": len(compiled), "agree": len(same),
            "op_time_share_agreeing": (sum(v for n, v in op_s.items()
                                           if n in same) / total
                                       if total else None)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-dir", required=True)
    ap.add_argument("--out")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--as-text", action="store_true",
                    help="compile the pqe tick program again and compare "
                         "its op names with the profile's")
    args = ap.parse_args(argv)

    from bench import harness, scopes, tiny
    from bench import trace as trace_mod
    from bench.run import tpu_devices

    with contextlib.ExitStack() as stack:
        bench_dir = harness.BENCH_DIR
        if args.tiny:
            bench_dir = Path(stack.enter_context(tempfile.TemporaryDirectory()))
            bench = tiny.bench_tree(bench_dir)
        else:
            bench = harness.load_benchmark(ROOT)
        devices = tpu_devices(int(harness.cell_of(bench, args.workload)["chips"]))
        import jax

        from repro.core import obs

        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # the end-to-end metrics too, in the traced result line
        bench["per_layer"] = [dict(m, workloads=[args.workload])
                              for m in bench["end_to_end"] + bench["per_layer"]]
        stop_s: list = []
        harness._profiled = _profiled_with_hlo(stop_s)
        engines = []

        def make(config, devs):
            engines.append(harness.build_engine(config, devs))
            return engines[-1]

        lines: list = []

        def emit(s):
            lines.append(s)
            print(s, flush=True)

        with obs.gc_spans():
            result = harness.run_cell(
                bench, args.workload, seed=args.seed, seconds=args.seconds,
                traced=True, devices=devices, bench_dir=bench_dir,
                make=make, t_start=T_START, trace_dir=args.trace_dir,
                emit=emit)
        ticks = next(int(s.split("ticks=")[1].split()[0]) for s in lines
                     if s.startswith("# window_s="))
        xplane = trace_mod.find_xplane(args.trace_dir)
        scoped = {"stop_trace_s": stop_s[0] if stop_s else None,
                  "xplane_bytes": xplane.stat().st_size, "ticks": ticks}
        t = time.perf_counter()
        op_s = {}
        try:
            summary = scopes.reduce(scopes.load(xplane))
        except ValueError as e:         # no window or no device op
            scoped["error"] = str(e)
        else:
            op_s = summary.base.op_s
            ops = sorted(summary.base.op_s.items(), key=lambda kv: -kv[1])
            scoped.update(reduce_s=time.perf_counter() - t,
                          busy_s=summary.base.busy_s,
                          window_s=summary.base.window_s,
                          metrics=summary.per_tick_ms(ticks),
                          breakdown=summary.breakdown(),
                          launch_idle_s=summary.launch_idle_s,
                          launch_s=summary.launch_s,
                          program_idle_s=summary.program_idle_s,
                          top_ops=[[n, v, summary.op_scopes.get(n)]
                                   for n, v in ops[:16]])
        if args.as_text and engines and engines[0].kind == "pqe":
            scoped["as_text"] = _as_text_matches(engines[0], xplane, op_s)
    result["scoped"] = scoped
    line = json.dumps(result)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
