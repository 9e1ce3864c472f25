"""SLA harness: every request to exactly one outcome, quantiles per budget.

The metric that matters under load is the latency DISTRIBUTION served,
not raw throughput (arXiv:1509.07053): a queue that "keeps up" by
letting p99 diverge has failed its users.  :func:`run_sla` drives a
:class:`~repro.serving.engine.RequestEngine` for a fixed number of
ticks, drains the backlog, flushes the retry buffer, and returns a
record in which

    arrivals == served + shed + expired        (exact, checked)

— the outcome partition of DESIGN.md §8 — together with time-to-serve
p50 / p99 / p99.9 of the SERVED class, measured on the simulated clock
(ticks, not wall time: deterministic given the seed, so the numbers are
machine-independent and benchmark cells built on them are gateable).

:func:`build_engine` assembles the standard stack for benchmarks and
tests: DistShardedQueue -> ElasticDistQueue (optionally chaos-scheduled)
-> RequestEngine, with arrival rate expressed as utilization
``rho = rate / serve_rate`` (rho 0.7 = steady state, 1.5 = overload the
admission layer must shed ~1/3 of).
"""

from __future__ import annotations

from typing import Optional

from repro.core.config import PQConfig
from repro.core.factory import EngineSpec, make_engine
from repro.ft.inject import FaultSchedule
from repro.serving.arrivals import (
    ArrivalProcess, BurstyArrivals, DiurnalArrivals, PoissonArrivals)
from repro.serving.engine import RequestEngine
from repro.serving.scheduler import SHED, OverloadPolicy, QualityPolicy

_PATTERNS = {"poisson": PoissonArrivals, "bursty": BurstyArrivals,
             "diurnal": DiurnalArrivals}


def build_engine(*, n_devices: int = 1, lanes_per_device: int = 4,
                 width: int = 64, rho: float = 0.7, n_slots: int = 8,
                 pattern: str = "poisson", seed: int = 0,
                 schedule: Optional[FaultSchedule] = None,
                 spare_devices: int = 0, depth_cap: Optional[int] = None,
                 tick_dt: float = 1.0, slack: float = 1.0,
                 sla_mean: float = 50.0, sla_min: float = 20.0,
                 p_urgent: float = 0.0, max_retries: int = 2,
                 preroute: str = "adaptive",
                 quality: Optional[dict] = None,
                 **arrival_kw) -> RequestEngine:
    """Assemble queue -> elastic controller -> engine at utilization
    ``rho`` (arrival rate = rho * n_slots / tick_dt).

    ``depth_cap`` defaults to half the queue's structural floor
    (n_lanes * seq_cap), far below where the router could drop —
    admission is meant to bind FIRST.  Pass ``schedule`` (or build one
    from ``PQ_CHAOS`` via :func:`repro.ft.inject.parse_chaos`) for chaos
    runs; ``spare_devices`` must then cover the kills.  ``quality``
    (a :class:`~repro.serving.scheduler.QualityPolicy` or its kwargs
    dict, e.g. ``dict(max_defer=3, defer_frac=0.5)``) enables the
    quality-relaxed serving mode: deadline slack is spent on deferred,
    coalesced serve rounds (DESIGN.md §12).
    """
    if pattern not in _PATTERNS:
        raise ValueError(f"unknown arrival pattern {pattern!r} "
                         f"(have {sorted(_PATTERNS)})")
    base = PQConfig(a_max=width, r_max=width, seq_cap=4 * width + 2,
                    n_buckets=8, bucket_cap=width, detach_min=8,
                    detach_max=256, detach_init=8, chop_patience=64)
    ctl = make_engine(
        EngineSpec(engine="elastic", width=width, base=base,
                   lanes=n_devices * lanes_per_device,
                   n_devices=n_devices, lanes_per_device=lanes_per_device,
                   spare_devices=spare_devices, preroute=preroute),
        schedule=schedule, seed=seed, tick_dt=tick_dt)
    if depth_cap is None:
        shard = ctl.queue.cfg.shard
        depth_cap = (shard.n_lanes * shard.lane.seq_cap) // 2
    policy = OverloadPolicy(depth_cap=depth_cap, serve_rate=float(n_slots),
                            tick_dt=tick_dt, slack=slack,
                            max_retries=max_retries)
    arrivals = _PATTERNS[pattern](
        rho * n_slots / tick_dt, clock=ctl.clock, tick_dt=tick_dt,
        seed=seed, sla_mean=sla_mean, sla_min=sla_min, p_urgent=p_urgent,
        **arrival_kw)
    if quality is not None and not isinstance(quality, QualityPolicy):
        quality = QualityPolicy(**quality)
    return RequestEngine(ctl, policy, arrivals=arrivals, n_slots=n_slots,
                         quality=quality)


def run_sla(engine: RequestEngine, n_ticks: int, *,
            drain: bool = True, max_drain_ticks: int = 10_000) -> dict:
    """Drive ``n_ticks`` arrival rounds, then (by default) drain the
    backlog and flush the retry buffer so the partition is exact.

    Returns the engine report plus the run shape; raises
    ``RuntimeError`` unless the conservation contract ``arrivals ==
    served + shed + expired`` holds when drained (with the residual
    classes when not).
    """
    for _ in range(n_ticks):
        engine.tick()
    drain_ticks = 0
    if drain:
        # drain feeds empty waves, so the attached arrival process is
        # not consulted; parked retries re-offer as they come due and
        # either serve or shed.  flush() terminates any stragglers so
        # the partition is exact.
        drain_ticks = engine.drain(max_ticks=max_drain_ticks)
        for _ev in engine.admission.flush(engine.clock.now):
            engine.outcomes[SHED] += 1
    rep = engine.report()
    rep["n_ticks"] = n_ticks
    rep["drain_ticks"] = drain_ticks
    total = rep["served"] + rep["shed"] + rep["expired"]
    if not drain:
        total += rep["in_flight"] + rep["retry_pending"]
    if total != rep["arrivals"]:
        raise RuntimeError(
            f"outcome partition broken: {total} != {rep['arrivals']}")
    return rep
