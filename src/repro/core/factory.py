"""Unified engine factory: one spec resolves every queue engine.

The repo grew five ways to construct a queue — ``PQConfig`` + module
functions (pqe), ``make_sharded_cfg`` (lanes), ``make_dist_cfg`` +
``DistShardedQueue`` (mesh), ``ElasticDistQueue(...)`` (fault
tolerance), and now the adaptive workload controller — and ~32 call
sites each hard-coded one of them.  The paper's point is that the
winning structure is *workload-dependent* (MultiQueues, arXiv:1411.1209;
Practical Concurrent Priority Queues, arXiv:1509.07053), so engine
choice must be a runtime value behind one API, not a call-site
constant.  This module is that API, the registry-based factory pattern
(cf. the xFormers block factory)::

    from repro.core.factory import EngineSpec, make_engine

    eng = make_engine(EngineSpec(engine="sharded", width=4096, lanes=8))
    state = eng.init(seed=0)
    state, res = eng.tick(state, keys, vals, mask, rm_count)

Every engine satisfies the :class:`QueueEngine` protocol
(``init / tick / tick_n / stats / resident / relax_bound / width``), so
drivers — ``bench_mix``, the serving engine, the examples — never
isinstance-dispatch on concrete classes: a driver written once runs the
paper's combined queue, the relaxed lanes, the device mesh, and the
workload controller unchanged::

    for spec in (EngineSpec(engine="pqe", width=64),
                 EngineSpec(engine="sharded", width=64, lanes=4),
                 EngineSpec(engine="adaptive", width=64, lanes=4)):
        eng = make_engine(spec)
        state = eng.init(seed=0)
        state, res = eng.tick(state, keys, vals, mask, rm_count)
        served = res.rm_keys[res.rm_served]       # within the c smallest
        assert eng.relax_bound(8) >= 8            # c of the contract

``EngineSpec(quality_budget=...)`` caps the relaxation the built engine
may spend: the lane count is clamped to the widest L whose analytic
rank-error envelope (``relax_bound(r) - r`` at r = W; DESIGN.md §12)
fits the budget — budget 0 forces an exact engine.  The envelope is
adversarial and nearly flat in L; for measured, graded tuning use
:func:`repro.quality.tuner.tune_lanes`.

The legacy constructors (``make_sharded_cfg``, ``make_dist_cfg``)
survive one PR as deprecated aliases; tests/test_factory.py asserts no
in-repo caller still uses them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import obs, pqueue
from repro.core import sharded as shq
from repro.core.config import PQConfig


@runtime_checkable
class QueueEngine(Protocol):
    """What every queue engine exposes (structural, checked at runtime).

    ``tick`` donates ``state`` and returns ``(new_state, result)`` with
    a ``rm_keys / rm_vals / rm_served`` result; ``tick_n`` is the
    scan-driver twin over [T, ...]-stacked batches.  ``resident``
    enumerates ``(keys, vals, live)`` of everything stored (the drain
    surface of the adaptive controller's engine switch), and
    ``relax_bound(r)`` is the c of the c-relaxed remove contract — r
    itself for exact engines.
    """

    def init(self, *, seed: int = 0) -> Any: ...

    def tick(self, state, add_keys, add_vals, add_mask, rm_count): ...

    def tick_n(self, state, add_keys, add_vals, add_mask, rm_counts): ...

    def stats(self, state) -> Any: ...

    def resident(self, state): ...

    def relax_bound(self, rm_count: int) -> int: ...


#: PQConfig knobs of the paper's §2.1 adaptive moveHead policy — settable
#: straight on the spec so the policy is a first-class engine parameter
#: rather than a buried config literal (see core/adaptive.update_detach).
_DETACH_KNOBS = (
    "detach_min",
    "detach_max",
    "detach_init",
    "halve_threshold",
    "double_threshold",
)


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """One config object for every engine kind.

    ``engine`` picks the registry entry (``pqe | sharded | dist |
    elastic | adaptive`` plus the bench baselines); the remaining fields
    are interpreted by the builders that need them and ignored by the
    rest — the same shape-one-spec pattern as the xFormers factories.
    """

    engine: str = "pqe"
    width: int = 256  # op-batch width W per tick
    base: Optional[PQConfig] = None  # None -> default_base(width)

    # kernel backend: "jnp" | "pallas" | "pallas_interpret" | "auto" (or a
    # resolved repro.kernels.ops.KernelBackend); validated + resolved ONCE
    # in resolved_base(), so dispatch is part of the engine's config — the
    # compiled tick's cache key — never a per-call string or an ambient
    # jax.default_backend() probe.  None keeps the base config's backend.
    backend: Optional[Any] = None

    # lane geometry (sharded / dist / elastic / adaptive); min_lanes is
    # fold headroom — quotas sized so the queue can fold down to it
    lanes: int = 4
    min_lanes: Optional[int] = None
    slack: float = 1.0
    preroute: str = "adaptive"

    # mesh placement (dist / elastic)
    n_devices: int = 1
    lanes_per_device: Optional[int] = None  # None -> lanes // n_devices
    spare_devices: int = 0
    axis: str = "data"

    # paper §2.1 adaptive-detach knobs; None keeps the base config value
    detach_min: Optional[int] = None
    detach_max: Optional[int] = None
    detach_init: Optional[int] = None
    halve_threshold: Optional[int] = None
    double_threshold: Optional[int] = None

    # workload controller (adaptive / elastic); a
    # repro.core.adaptive.ControllerConfig or None for defaults
    controller: Any = None

    # rank-error budget (sharded / adaptive): clamp lanes so the
    # analytic envelope relax_bound(W) - W fits it (None = unbudgeted;
    # see lanes_within_budget and DESIGN.md §12)
    quality_budget: Optional[float] = None


def default_base(width: int) -> PQConfig:
    """A width-`width` single-queue base config (the bench geometry)."""
    return PQConfig(
        a_max=width,
        r_max=width,
        seq_cap=max(4096, 4 * width),
        n_buckets=64,
        bucket_cap=max(64, width // 32),
        detach_min=8,
        detach_max=65536,
        detach_init=256,
        halve_threshold=1000,
        double_threshold=100,
    )


def resolved_base(spec: EngineSpec) -> PQConfig:
    """The spec's base config with its detach knobs and backend applied.

    ``spec.backend`` is validated here (``jnp | pallas | pallas_interpret
    | auto`` or an already-resolved ``KernelBackend``) and resolved
    eagerly via :func:`repro.kernels.ops.resolve_backend` — every engine
    builder funnels through this function, so backend selection flows
    from the spec into ``PQConfig.backend`` exactly once, at construction.
    """
    from repro.kernels.ops import resolve_backend

    base = spec.base if spec.base is not None else default_base(spec.width)
    over = {
        k: getattr(spec, k) for k in _DETACH_KNOBS if getattr(spec, k) is not None
    }
    if spec.backend is not None:
        over["backend"] = resolve_backend(spec.backend)
    return dataclasses.replace(base, **over) if over else base


def lanes_within_budget(spec: EngineSpec, lanes: int) -> int:
    """Widest lane count <= ``lanes`` whose analytic rank-error envelope
    fits ``spec.quality_budget`` (identity when the spec is unbudgeted).

    The envelope is ``relax_bound(cfg_L, W) - W`` — the adversarial
    worst-case displacement of any served key beyond the exact prefix
    (DESIGN.md §12), evaluated at the widest per-tick request r = W.
    L = 1 has envelope 0 (exact), so the walk always terminates.  This
    is the ENVELOPE inversion: nearly binary in L for the bench geometry
    (quotas size ``L * lane.a_max ~= W``, so every L >= 2 costs about
    ``W + 2W``); :func:`repro.quality.tuner.tune_lanes` is the measured,
    graded instrument on an actual workload.
    """
    if spec.quality_budget is None:
        return lanes
    budget = float(spec.quality_budget)
    base = resolved_base(spec)
    ml = spec.min_lanes
    for ln in range(lanes, 0, -1):
        cfg = shq._sharded_cfg(
            spec.width,
            ln,
            base=base,
            slack=spec.slack,
            min_lanes=None if ml is None else min(ml, ln),
            preroute=spec.preroute,
        )
        if shq.relax_bound(cfg, spec.width) - spec.width <= budget:
            return ln
    return 1


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[..., Any]] = {}


def register(name: str):
    """Register an engine builder ``(spec, **kw) -> QueueEngine``."""

    def deco(fn):
        _REGISTRY[name] = fn
        return fn

    return deco


def engine_kinds():
    return sorted(_REGISTRY)


def make_engine(spec: EngineSpec, **kw) -> QueueEngine:
    """Resolve ``spec.engine`` through the registry and build the engine.

    Keyword arguments pass through to the builder (``mesh=`` for dist,
    ``schedule= / seed= / tick_dt=`` etc. for elastic); builders raise on
    keywords they do not understand.
    """
    try:
        build = _REGISTRY[spec.engine]
    except KeyError:
        raise ValueError(
            f"unknown engine {spec.engine!r} (have {engine_kinds()})"
        ) from None
    return build(spec, **kw)


# ---------------------------------------------------------------------------
# adapters: module-function engines behind the protocol
# ---------------------------------------------------------------------------


class PQEngine:
    """The paper's combined queue (repro.core.pqueue) as an engine."""

    kind = "pqe"

    def __init__(self, cfg: PQConfig):
        self.cfg = cfg

    @property
    def width(self) -> int:
        return self.cfg.a_max

    def init(self, *, seed: int = 0):
        del seed  # deterministic structure, no router PRNG
        return pqueue.init(self.cfg)

    def tick(self, state, add_keys, add_vals, add_mask, rm_count):
        with obs.span(obs.SPAN_TICK):
            return pqueue.tick(self.cfg, state, add_keys, add_vals, add_mask, rm_count)

    def tick_n(self, state, add_keys, add_vals, add_mask, rm_counts):
        with obs.span(obs.SPAN_TICK_N):
            return pqueue.tick_n(
                self.cfg, state, add_keys, add_vals, add_mask, rm_counts
            )

    def stats(self, state):
        return state.stats

    def resident(self, state):
        return pqueue.resident(self.cfg, state)

    def relax_bound(self, rm_count: int) -> int:
        return int(rm_count)  # exact queue: removes are true minima

    def size(self, state):
        return pqueue.size(state)


class ShardedEngine:
    """The L-lane relaxed queue (repro.core.sharded) as an engine."""

    kind = "sharded"

    def __init__(self, cfg: shq.ShardedPQConfig):
        self.cfg = cfg

    @property
    def width(self) -> int:
        return self.cfg.a_total

    def init(self, *, seed: int = 0):
        return shq.init(self.cfg, seed=seed)

    def tick(self, state, add_keys, add_vals, add_mask, rm_count):
        with obs.span(obs.SPAN_TICK):
            return shq.tick(self.cfg, state, add_keys, add_vals, add_mask, rm_count)

    def tick_n(self, state, add_keys, add_vals, add_mask, rm_counts):
        with obs.span(obs.SPAN_TICK_N):
            return shq.tick_n(self.cfg, state, add_keys, add_vals, add_mask, rm_counts)

    def stats(self, state):
        return shq.stats(state)

    def resident(self, state):
        return shq.resident(self.cfg, state.lanes)

    def relax_bound(self, rm_count: int) -> int:
        return shq.relax_bound(self.cfg, rm_count)

    def size(self, state):
        return shq.size(state)


class BaselineEngine:
    """The paper's §4 baselines (FCPQ / ParallelPQ) behind the same
    surface — enough protocol for the bench driver (no scan driver, no
    resident enumeration: they exist to be measured, not managed)."""

    def __init__(self, kind: str, cfg: PQConfig, impl):
        self.kind = kind
        self.cfg = cfg
        self._impl = impl

    @property
    def width(self) -> int:
        return self.cfg.a_max

    def init(self, *, seed: int = 0):
        del seed
        return self._impl.init(self.cfg)

    def tick(self, state, add_keys, add_vals, add_mask, rm_count):
        with obs.span(obs.SPAN_TICK):
            return self._impl.tick(
                self.cfg, state, add_keys, add_vals, add_mask, rm_count
            )

    def tick_n(self, state, add_keys, add_vals, add_mask, rm_counts):
        results = []
        for t in range(add_keys.shape[0]):
            state, res = self.tick(
                state, add_keys[t], add_vals[t], add_mask[t], rm_counts[t]
            )
            results.append(res)
        if not results:
            return state, None
        return state, jax.tree.map(lambda *xs: jnp.stack(xs), *results)

    def stats(self, state):
        return None

    def resident(self, state):
        raise NotImplementedError(f"{self.kind} keeps no drain surface")

    def relax_bound(self, rm_count: int) -> int:
        return int(rm_count)

    def size(self, state):
        return self._impl.size(state)


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------


@register("pqe")
def _build_pqe(spec: EngineSpec) -> PQEngine:
    return PQEngine(resolved_base(spec))


@register("sharded")
def _build_sharded(spec: EngineSpec) -> ShardedEngine:
    lanes = lanes_within_budget(spec, spec.lanes)
    ml = spec.min_lanes
    cfg = shq._sharded_cfg(
        spec.width,
        lanes,
        base=resolved_base(spec),
        slack=spec.slack,
        min_lanes=None if ml is None else min(ml, lanes),
        preroute=spec.preroute,
    )
    return ShardedEngine(cfg)


@register("fcskiplist")
def _build_fc(spec: EngineSpec) -> BaselineEngine:
    from repro.core.baselines import FCPQ

    return BaselineEngine("fcskiplist", resolved_base(spec), FCPQ)


@register("lfskiplist")
def _build_lf(spec: EngineSpec) -> BaselineEngine:
    from repro.core.baselines import ParallelPQ

    return BaselineEngine("lfskiplist", resolved_base(spec), ParallelPQ)


def _dist_cfg_of(spec: EngineSpec):
    # lazy import: distributed pulls in repro.dist.sharding (mesh deps)
    from repro.core import distributed as dq

    lpd = spec.lanes_per_device
    if lpd is None:
        if spec.lanes % spec.n_devices:
            raise ValueError(
                f"lanes ({spec.lanes}) must divide evenly across "
                f"n_devices ({spec.n_devices}); or set lanes_per_device"
            )
        lpd = spec.lanes // spec.n_devices
    return dq._dist_cfg(
        spec.width,
        spec.n_devices,
        lpd,
        base=resolved_base(spec),
        slack=spec.slack,
        spare_devices=spec.spare_devices,
        preroute=spec.preroute,
        axis=spec.axis,
    )


@register("dist")
def _build_dist(spec: EngineSpec, *, mesh=None):
    from repro.core import distributed as dq

    return dq.DistShardedQueue(_dist_cfg_of(spec), mesh=mesh)


@register("elastic")
def _build_elastic(spec: EngineSpec, *, mesh=None, **elastic_kw):
    from repro.core import distributed as dq
    from repro.ft.elastic import ElasticDistQueue

    q = dq.DistShardedQueue(_dist_cfg_of(spec), mesh=mesh)
    return ElasticDistQueue(q, controller=spec.controller, **elastic_kw)


@register("adaptive")
def _build_adaptive(spec: EngineSpec):
    from repro.core import adaptive

    return adaptive.AdaptiveEngine(spec)
