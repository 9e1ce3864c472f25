"""One run of one benchmark cell: build, load, warm, time, check.

:func:`run_cell` does the steps of a run in order:

1. builds the engine through ``repro.core.factory.make_engine`` from the
   configuration's spec (a mesh over the given devices for ``dist``);
2. makes the traffic from the seed (:mod:`bench.generate`);
3. loads the resident keys with zero-remove ticks;
4. runs a few mix ticks, so that the one compiled tick program is warm;
5. runs the timed window: one ``[1, W]`` ``tick_n`` per dispatch, each
   waited for and its results pulled to the host before the next batch
   is sent (a closed loop, as a discrete-event simulator or a
   scheduling loop drives a queue), until ``seconds`` have passed;
6. checks every tick of the run against the exact reference
   (:mod:`bench.reference`) under the configuration's guarantee, and the
   queue's resident pairs and ``size()`` after the window.

The metrics are reduced from the run's :class:`Observation` by the
readers in ``bench/metrics/``, found by the names in ``BENCHMARK.json``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import generate, reference
from bench import trace as trace_mod

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

#: mix ticks run after the load and before the window
WARM_TICKS = 2
#: most blocks of traffic made before the window; more are made inside
#: it, under their own "generate" span
MAX_PREMADE_BLOCKS = 256


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def cell_of(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


#: the guarantees a configuration may state
ORDERS = ("exact", "c-relaxed")


def load_config(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    with open(Path(bench_dir) / "configs" / f"{name}.json") as f:
        config = json.load(f)
    g = config["guarantee"]
    if g["order"] not in ORDERS:
        raise ValueError(f"{name}: unknown guarantee {g['order']!r}")
    if g["order"] == "exact" and g["rank_err_max"] != 0:
        raise ValueError(f"{name}: an exact order allows no rank error")
    return config


def load_mix(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    with open(Path(bench_dir) / "traffic" / f"{name}.json") as f:
        return json.load(f)


def load_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """The ``read(obs)`` of ``bench/metrics/<name>.py``."""
    path = Path(bench_dir) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(bench: dict, cell: str, traced: bool) -> List[dict]:
    """The cell's end-to-end metrics, or with ``traced`` its per-layer
    ones: those that list the cell, or list no cells."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def engine_spec(config: dict):
    """The ``EngineSpec`` that the configuration's file states."""
    from repro.core.config import PQConfig
    from repro.core.factory import EngineSpec

    fields = dict(config["spec"])
    fields["base"] = PQConfig(**fields["base"])
    return EngineSpec(**fields)


def build_engine(config: dict, devices):
    """``make_engine`` of the configuration, over ``devices``."""
    from repro.core.factory import make_engine

    spec = engine_spec(config)
    if spec.engine == "dist":
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(devices[:spec.n_devices]), (spec.axis,))
        return make_engine(spec, mesh=mesh)
    return make_engine(spec)


def read_counters(eng, state) -> Optional[Dict[str, float]]:
    """The engine's cumulative counters as host numbers, lane counters
    summed (``None`` for an engine without counters)."""
    st = eng.stats(state)
    if st is None:
        return None
    out = {}
    for name, value in st._asdict().items():
        if hasattr(value, "_asdict"):
            out.update({k: float(np.asarray(v).sum())
                        for k, v in value._asdict().items()})
        else:
            out[name] = float(np.asarray(value).sum())
    return out


class CompileClock:
    """Count and seconds of XLA backend compiles, from JAX's own
    monitoring event; a program found in the persistent cache does not
    fire it."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def lap(self):
        out = (self.count, self.seconds)
        self.count, self.seconds = 0, 0.0
        return out


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Observation:
    """What one run saw; the metric readers take their numbers from it."""

    setup_s: float
    window_s: float                 # host clock, first dispatch to last pull
    ticks: int                      # ticks in the window
    live_adds: int                  # live adds sent in the window
    served: int                     # removes served in the window
    tick_s: np.ndarray              # per tick: dispatch to results on host
    rank_err: np.ndarray            # per key served in the window
    counters: Optional[Dict[str, float]]   # window delta; None: no counters
    trace: Optional[trace_mod.TraceSummary]


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


class _Log:
    """Every tick of the run, as sent and as served, for the check."""

    def __init__(self):
        self.add_keys: list = []
        self.add_ids: list = []
        self.rm: list = []
        self.served_keys: list = []
        self.served_ids: list = []

    def __len__(self):
        return len(self.rm)

    def append(self, add_keys, add_ids, rm, served_keys, served_ids):
        self.add_keys.append(add_keys)
        self.add_ids.append(add_ids)
        self.rm.append(rm)
        self.served_keys.append(served_keys)
        self.served_ids.append(served_ids)


class _Loop:
    """Sends one ``[1, W]`` batch per dispatch and pulls its results."""

    def __init__(self, eng, state, traffic: generate.Traffic,
                 pool: generate.IdPool, log: _Log):
        self.eng, self.state = eng, state
        self.traffic, self.pool, self.log = traffic, pool, log
        w = eng.width
        self.keys = np.full((1, w), np.inf, np.float32)
        self.vals = np.full((1, w), -1, np.int32)
        self.mask = np.zeros((1, w), bool)
        self._rm = np.zeros((1,), np.int32)
        self._blocks: Dict[int, generate.Block] = {}
        self.mix_tick = 0           # next mix tick to send
        self.gen_s = 0.0            # seconds spent making blocks
        self._served_ids = np.empty(0, np.int32)
        self._served_keys = np.empty(0, np.float32)
        self.clock = traffic.clock0     # the largest key served so far

    def premake(self, n_ticks: int) -> None:
        last = min((self.mix_tick + n_ticks) // generate.BLOCK,
                   self.mix_tick // generate.BLOCK + MAX_PREMADE_BLOCKS)
        for b in range(self.mix_tick // generate.BLOCK, last + 1):
            self._block(b)

    def _block(self, b: int) -> generate.Block:
        if b not in self._blocks:
            t = time.perf_counter()
            self._blocks[b] = self.traffic.block(b)
            self.gen_s += time.perf_counter() - t
        return self._blocks[b]

    def _send(self, keys, rm: int):
        """One tick: the adds ``keys`` (ids from the pool) and ``rm``
        removes.  Returns the seconds from dispatch to results on host."""
        import jax

        n = keys.size
        ids = self.pool.take(n)
        self.keys[0, :n] = keys
        self.keys[0, n:] = np.inf
        self.vals[0, :n] = ids
        self.vals[0, n:] = -1
        self.mask[0, :] = False
        self.mask[0, :n] = True
        self._rm[0] = rm
        t0 = time.perf_counter()
        with _span("dispatch"):
            self.state, res = self.eng.tick_n(self.state, self.keys,
                                              self.vals, self.mask, self._rm)
            jax.block_until_ready(res)
        with _span("pull"):
            rk = np.asarray(res.rm_keys)[0]
            rv = np.asarray(res.rm_vals)[0]
            rs = np.asarray(res.rm_served)[0]
        dt = time.perf_counter() - t0
        self._served_keys, self._served_ids = rk[rs], rv[rs]
        self.log.append(keys, ids, rm, self._served_keys, self._served_ids)
        return dt

    def load(self, keys: np.ndarray) -> None:
        w = self.eng.width
        for i in range(0, keys.size, w):
            self.pool.give(self._served_ids)
            self._send(keys[i:i + w], 0)

    def mix(self) -> float:
        """One tick of the mix; returns its dispatch-to-host seconds.
        The tick's keys follow the clock that the ticks before it served
        up to: the loop is closed."""
        with _span("generate"):
            self.pool.give(self._served_ids)
            served = self._served_keys[np.isfinite(self._served_keys)]
            if served.size:
                self.clock = max(self.clock, float(served.max()))
            b, i = divmod(self.mix_tick, generate.BLOCK)
            keys = self.traffic.keys(self._block(b), i, self.clock)
            if i == generate.BLOCK - 1:
                del self._blocks[b]
            self.mix_tick += 1
        return self._send(keys, self.traffic.n_rm)


@contextlib.contextmanager
def _profiled(log_dir: Optional[str]):
    """Profile the block into ``log_dir``; no profile where it is None."""
    if log_dir is None:
        yield
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(bench: dict, cell_name: str, *, seed: int, seconds: float,
             traced: bool, devices, bench_dir: Path = BENCH_DIR,
             make: Callable = build_engine, t_start: Optional[float] = None,
             trace_dir: Optional[str] = None, emit=print) -> dict:
    """One run of one cell.  Returns the result object of the last
    line; ``emit`` gets the earlier lines."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    cell = cell_of(bench, cell_name)
    config = load_config(cell["config"], bench_dir)
    mix = load_mix(cell["traffic"], bench_dir)
    chips = int(cell["chips"])
    devices = list(devices)[:chips]
    clock = CompileClock()

    marks = [("start", time.perf_counter())]
    eng = make(config, devices)
    traffic = generate.Traffic(mix, width=eng.width,
                               resident=int(config["resident"]), seed=seed)
    pool = generate.IdPool(reference.ID_BOUND)
    log = _Log()
    drv = _Loop(eng, eng.init(seed=int(config["engine_seed"])), traffic,
                  pool, log)
    marks.append(("build", time.perf_counter()))
    drv.load(traffic.load_keys())
    n_load = len(log)
    marks.append(("load", time.perf_counter()))
    warm = [drv.mix() for _ in range(WARM_TICKS)]
    marks.append(("warm", time.perf_counter()))
    drv.premake(math.ceil(seconds / max(min(warm), 1e-6) * 1.5) + 1)
    before = read_counters(eng, drv.state)
    gen_before = drv.gen_s
    setup_compiles, setup_compile_s = clock.lap()

    lat: List[float] = []
    summary = None
    with contextlib.ExitStack() as stack:
        log_dir = trace_dir
        if traced and log_dir is None:
            log_dir = stack.enter_context(tempfile.TemporaryDirectory())
        with _profiled(log_dir if traced else None):
            jax.block_until_ready(drv.state)
            t0 = time.perf_counter()
            setup_s = t0 - t_start
            with _span("window"):
                while True:
                    lat.append(drv.mix())
                    t1 = time.perf_counter()
                    if t1 - t0 >= seconds:
                        break
        window_s = t1 - t0
        compiles, compile_s = clock.lap()
        if traced:
            try:
                summary = trace_mod.reduce(trace_mod.load(
                    trace_mod.find_xplane(log_dir)))
            except ValueError as e:
                emit(f"# trace: {e}")
    gen_s = drv.gen_s - gen_before
    emit(f"# setup_s={setup_s} setup_compiles={setup_compiles} "
         f"setup_compile_s={setup_compile_s} load_ticks={n_load}")
    emit("# setup_phases " + " ".join(
        f"{name}={t - t_start}" for name, t in marks))
    q = np.percentile(lat, [50, 90, 95, 99, 100]) * 1e3
    emit(f"# tick_ms mean={1e3 * window_s / len(lat)} p50={q[0]} p90={q[1]} "
         f"p95={q[2]} p99={q[3]} max={q[4]}")
    emit(f"# window_s={window_s} ticks={len(lat)} compiles_in_window="
         f"{compiles} compile_s_in_window={compile_s}")
    emit(f"# generator_s_in_window={gen_s} generator_share="
         f"{gen_s / window_s}")

    # -- after the window: memory, counters, the resident state --
    memory_peak = _memory_peak(devices)
    after = read_counters(eng, drv.state)
    rkeys, rvals, rlive = (np.asarray(x) for x in eng.resident(drv.state))
    size = int(np.asarray(eng.size(drv.state)))
    drv.state = None

    # -- the check, against the exact reference --
    with _span("check"):
        t_check = time.perf_counter()
        checks, rank_err, served_in_window, rank_over = _check(
            config, log, n_window=len(lat), counters=after,
            resident=(rkeys[rlive], rvals[rlive]), size=size)
        emit(f"# check_s={time.perf_counter() - t_check}")
    if rank_err.size:
        q = np.percentile(rank_err, [50, 90, 99, 100])
        emit(f"# rank_err mean={rank_err.mean()} p50={q[0]} p90={q[1]} "
             f"p99={q[2]} max={q[3]}")

    n_window = len(lat)
    live_adds = n_window * traffic.n_add
    delta = None
    if before is not None and after is not None:
        delta = {k: after[k] - before[k] for k in after}
    obs = Observation(
        setup_s=setup_s, window_s=window_s,
        ticks=n_window, live_adds=live_adds, served=served_in_window,
        tick_s=np.asarray(lat), rank_err=rank_err, counters=delta,
        trace=summary)
    metrics = {}
    for m in metrics_of(bench, cell_name, traced):
        value = load_reader(m["name"], bench_dir)(obs)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    if traced and summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    attempted = int(sum(a.size for a in log.add_keys) + sum(log.rm))
    failed = int(sum(c.value for c in checks if c.name != "rank_err_max")
                 + rank_over)
    result = {"correct": all(c.ok for c in checks), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if traced and summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def _check(config: dict, log: _Log, *, n_window: int, counters,
           resident, size: int):
    """Replay the run into the reference; returns the checks, the rank
    errors of the window's served keys, the count served in the window,
    and the count of served keys whose rank error passed the limit."""
    ref = reference.Reference()
    limit = float(config["guarantee"]["rank_err_max"])
    first_window = len(log) - n_window
    worst = 0
    over = 0
    window_err = []
    served = 0
    for t in range(len(log)):
        if log.rm[t] == 0 and not log.served_keys[t].size:
            ref.add(log.add_keys[t], log.add_ids[t])
            continue
        err = ref.tick(log.add_keys[t], log.add_ids[t], log.rm[t],
                       log.served_keys[t], log.served_ids[t])
        if err.size:
            worst = max(worst, int(err.max()))
            over += int((err > limit).sum())
        if t >= first_window:
            window_err.append(err)
            served += log.served_keys[t].size
    rkeys, rids = resident
    dropped = 0.0
    if counters is not None:
        dropped = counters.get("n_dropped", 0.0) + counters.get(
            "n_router_dropped", 0.0)
    checks = [
        Check("bad_pairs", ref.bad_pairs, 0),
        Check("count_gap", ref.count_gap, 0),
        Check("rank_err_max", worst, limit),
        Check("resident_gap", ref.resident_gap(rkeys, rids), 0),
        Check("size_gap", abs(size - len(ref)), 0),
        Check("dropped", dropped, 0),
    ]
    rank_err = (np.concatenate(window_err) if window_err
                else np.empty(0, np.int64))
    return checks, rank_err, served, over
