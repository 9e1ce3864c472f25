"""Distributed sharded priority queue: lanes-over-devices via shard_map.

This is the device-mesh port of :mod:`repro.core.sharded` (DESIGN.md
§3.4).  The L lanes of one :class:`~repro.core.sharded.ShardedPQConfig`
are placed across a D-device mesh as l = L / D device-local lanes; one
:func:`repro.dist.sharding.shard_map` tick runs the same synchronized
round the single-device queue runs, split into two planes:

* **Replicated control plane** — the stick-random router state (PRNG,
  route permutation, its stable inverse), the adaptive pre-route
  elimination pass and its controller EMAs, and the c-relaxed
  min-of-lane-heads grant allocation are all tiny O(W)/O(L) scalar math
  computed identically on every device from replicated inputs.  No
  coordinator exists: every device *derives* the same global decisions.
* **Device-sharded data plane** — the lanes themselves (every
  ``PQState`` leaf, sharded on the leading lane axis) and the expensive
  per-lane work: segment routing of the batch, the per-lane key sort,
  and the PR-2 batch-cond-hoisted lane ticks
  (:func:`repro.core.sharded._lanes_tick`, reused unchanged) run only
  over the device's own l lanes.

The only per-tick collectives are two all-gathers of per-device lane
summaries (head keys and sizes, O(L) scalars — equivalently a
``lax.pmin`` for the bound alone), so interconnect traffic is
independent of batch width, structure size, and tick payload:

* the **exact min-of-lane-heads bound** is the min of the gathered
  heads, so the c-relaxation contract (``sharded.relax_bound`` with the
  full L = D * l) is identical to single-device;
* **pre-route elimination** runs device-locally against that replicated
  global bound — matched pairs are served straight from the replicated
  batch and never touch the interconnect;
* **grants** come from the same replicated
  :func:`~repro.core.sharded._alloc_removes_arrays` allocation over the
  gathered [L] summaries; each device slices its own lanes' grants;
* **removeMin results assemble without a coordinator**: every lane
  serves a dense prefix of its result row, so the global compacted
  stream is ragged-segment arithmetic over the lane counts
  (:func:`~repro.core.sharded._fold_results`) — the lane segments land
  at the exclusive prefix over per-device serve counts.

Because every per-lane computation is bit-identical to the
single-device queue's (the batch-level cond hoists are
performance-only; see tests/test_tick_repairs.py), a
``DistShardedQueue`` over D devices serves the same stream as
single-device ``sharded`` with L = D * l lanes on the same op stream —
pinned per tick by tests/test_dist_sharded.py and the CI
``tests-multidev`` leg.

This module replaced the seed-era v1 (replicated combine over one
global pqueue tick) and v2 (device-sharded parallel part) distributed
ticks, which ran the pre-PR-2 tick and funneled every surviving op
through an O(W)-payload all-gather; see DESIGN.md §3.4 for the
collective cost comparison.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import obs, sharded
from repro.core.config import EMPTY_VAL, PQConfig
from repro.core.sharded import ShardedPQConfig, ShardedState, ShardedTickResult
from repro.dist.sharding import shard_map

INF = jnp.inf
_I32 = jnp.int32
_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class DistShardedPQConfig:
    """Static config of the lanes-over-devices queue.

    ``shard`` is the GLOBAL single-device-equivalent config: its
    ``n_lanes`` is the total L = n_devices * lanes_per_device, and its
    batch geometry (``a_total``) is the un-sharded op-batch width.  The
    equivalence contract is stated against ``sharded`` running this
    exact config on one device.
    """

    shard: ShardedPQConfig
    n_devices: int
    axis: str = "data"

    def __post_init__(self) -> None:
        if self.n_devices < 1:
            raise ValueError("n_devices must be >= 1")
        if self.shard.n_lanes % self.n_devices:
            raise ValueError(
                f"n_lanes ({self.shard.n_lanes}) must divide evenly "
                f"across n_devices ({self.n_devices})"
            )

    @property
    def lanes_per_device(self) -> int:
        return self.shard.n_lanes // self.n_devices

    # duck-typed batch geometry, same contract as ShardedPQConfig
    @property
    def a_max(self) -> int:
        return self.shard.a_total

    @property
    def r_max(self) -> int:
        return self.shard.a_total


def _dist_cfg(
    width: int,
    n_devices: int,
    lanes_per_device: int,
    *,
    base: PQConfig,
    slack: float = 1.0,
    spare_devices: int = 0,
    preroute: str = "adaptive",
    axis: str = "data",
) -> DistShardedPQConfig:
    """Scale a width-`width` single-queue config onto a D-device mesh.

    Per-lane geometry comes from :func:`sharded._sharded_cfg` with
    L = n_devices * lanes_per_device total lanes, so dist(D, l) and
    single-device sharded(L = D * l) share one config modulo placement.

    ``spare_devices`` sizes per-lane quotas for the elastic
    fault-tolerant path (:func:`resize`): quotas are computed as if only
    ``n_devices - spare_devices`` devices carried the full batch, so the
    queue can lose up to that many devices and the shrunken mesh's
    permuted round-robin still cannot overflow a lane (full-width
    re-insertion of a drained device stays drop-free, and the healthy
    queue keeps serving full batches through every intermediate size).
    """
    if not 0 <= spare_devices < n_devices:
        raise ValueError("spare_devices must be in [0, n_devices)")
    scfg = sharded._sharded_cfg(
        width,
        n_devices * lanes_per_device,
        base=base,
        slack=slack,
        min_lanes=(n_devices - spare_devices) * lanes_per_device,
        preroute=preroute,
    )
    return DistShardedPQConfig(shard=scfg, n_devices=n_devices, axis=axis)


def make_dist_cfg(*args, **kwargs) -> DistShardedPQConfig:
    """Deprecated alias of the dist config builder — construction now
    goes through :func:`repro.core.factory.make_engine`
    (``EngineSpec(engine="dist", ...)``).  Kept for one PR so external
    callers keep working; in-repo callers have been migrated."""
    import warnings

    warnings.warn(
        "make_dist_cfg is deprecated; use "
        "repro.core.factory.make_engine(EngineSpec(engine='dist', ...))",
        DeprecationWarning, stacklevel=2)
    return _dist_cfg(*args, **kwargs)


def _state_specs(axis: str) -> ShardedState:
    """shard_map pytree-prefix specs: lanes sharded on the leading lane
    axis, every control-plane leaf replicated."""
    return ShardedState(
        lanes=P(axis),
        rng=P(),
        route=P(),
        route_inv=P(),
        tick_idx=P(),
        n_router_dropped=P(),
        elim_ema=P(),
        balance_ema=P(),
        disp_ema=P(),
        n_preroute_elim=P(),
        n_preroute_ticks=P(),
    )


def default_mesh(cfg: DistShardedPQConfig) -> Mesh:
    """1-D mesh over the first ``cfg.n_devices`` local devices."""
    devs = jax.devices()
    if len(devs) < cfg.n_devices:
        raise ValueError(
            f"need {cfg.n_devices} devices, have {len(devs)} — force "
            "host devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N"
        )
    return Mesh(np.asarray(devs[: cfg.n_devices]), (cfg.axis,))


def _placement(cfg: DistShardedPQConfig, mesh: Mesh) -> ShardedState:
    """NamedSharding pytree matching :func:`_state_specs` on ``mesh``."""
    return ShardedState(
        lanes=NamedSharding(mesh, P(cfg.axis)),
        rng=NamedSharding(mesh, P()),
        route=NamedSharding(mesh, P()),
        route_inv=NamedSharding(mesh, P()),
        tick_idx=NamedSharding(mesh, P()),
        n_router_dropped=NamedSharding(mesh, P()),
        elim_ema=NamedSharding(mesh, P()),
        balance_ema=NamedSharding(mesh, P()),
        disp_ema=NamedSharding(mesh, P()),
        n_preroute_elim=NamedSharding(mesh, P()),
        n_preroute_ticks=NamedSharding(mesh, P()),
    )


def init(cfg: DistShardedPQConfig, mesh: Mesh, *, seed: int = 0) -> ShardedState:
    """Queue state placed on the mesh: the pytree is bit-identical to
    ``sharded.init(cfg.shard, seed=seed)`` — only the sharding differs
    (lanes split over devices, control plane replicated), so every
    ``sharded`` introspection helper (stats/size/lane_sizes) works on
    it unchanged."""
    state = sharded.init(cfg.shard, seed=seed)
    return jax.device_put(state, _placement(cfg, mesh))


def _dist_tick_body(
    scfg: ShardedPQConfig,
    n_local: int,
    axis: str,
    state: ShardedState,
    add_keys,
    add_vals,
    add_mask,
    rm_count,
    lane_scale,
):
    """Per-device body (under shard_map): the sharded tick with the lane
    axis cut to this device's ``n_local`` lanes.

    Mirrors :func:`sharded._tick_impl` stage by stage; every replicated
    value is computed identically on all devices (no collective), and
    the two all-gathers below are the tick's entire interconnect
    footprint.  Collectives sit OUTSIDE every data-dependent cond — a
    device-varying predicate around a collective would deadlock the
    SPMD program.

    ``lane_scale`` ([L] f32, replicated) is the degraded-mode grant
    throttle (repro.ft): each lane's grant cap is ``ceil(scale * r_max)``
    — all-ones is bit-identical to the unthrottled tick, a fractional
    scale sheds that lane's serve work onto healthy lanes through the
    allocator's water-fill, and any positive scale keeps the lane
    draining (ceil, so the cap never silently rounds to zero).
    """
    L = scfg.n_lanes
    lc = scfg.lane
    rl = lc.r_max
    w = add_keys.shape[0]
    out_w = max(w, L * rl)
    rm_count = jnp.minimum(jnp.asarray(rm_count, _I32), out_w)
    grant_cap = jnp.ceil(jnp.asarray(lane_scale, _F32) * rl).astype(_I32)
    my = jax.lax.axis_index(axis)
    lane_lo = my.astype(_I32) * n_local
    local = state.lanes  # PQState stack, leaves lead-dim n_local

    # -- the tick's only collectives: per-device lane summaries -> the
    # replicated [L] vectors behind the global bound and the grant
    # allocation (O(L) scalars, independent of batch width) --
    with jax.named_scope(obs.DQ_GATHER):
        min_v = jax.lax.all_gather(local.min_value, axis).reshape(-1)
        sizes_loc = local.seq_len + local.par_count
        sizes_pre = jax.lax.all_gather(sizes_loc, axis).reshape(-1)
    union_min = jnp.min(min_v)

    # -- pre-route elimination, device-local against the replicated
    # global bound: matched pairs are served from the replicated batch
    # and never touch the interconnect --
    n_adds_in = add_mask.sum(dtype=_I32)
    in_keys, in_mask = add_keys, add_mask  # raw batch for the dispersion EMA
    (
        add_keys,
        add_vals,
        add_mask,
        rm_residual,
        matched_k,
        matched_v,
        n_matched,
        elim_ran,
    ) = sharded._preroute_eliminate(
        scfg, state, add_keys, add_vals, add_mask, rm_count, union_min=union_min
    )
    elim_ema, balance_ema, disp_ema = sharded._controller_update(
        scfg, state, in_keys, in_mask, n_adds_in, rm_count, n_matched, elim_ran
    )

    # -- stick-random router refresh: replicated PRNG math, identical
    # on every device (same key -> same permutation) --
    resample = (state.tick_idx % scfg.stick) == 0

    @jax.named_scope(obs.SQ_ROUTE)
    def _resample(k):
        k2, sub = jax.random.split(k)
        fresh = sharded._fresh_route(sub, w, L)
        return k2, fresh, jnp.argsort(fresh, stable=True).astype(_I32)

    def _keep(k):
        return k, state.route, state.route_inv

    key, route, route_inv = jax.lax.cond(resample, _resample, _keep, state.rng)

    # -- replicated routing summary (counting only — actual routing of
    # the batch happens device-locally under the lane-work cond): live
    # adds per lane feed grant `incoming` and the drop counter --
    counts = sharded._route_counts(scfg, route_inv, add_mask)
    incoming = jnp.minimum(counts, lc.a_max)
    n_drop = jnp.sum(jnp.maximum(counts - lc.a_max, 0), dtype=_I32)

    # -- replicated grant allocation over the gathered summaries; each
    # device slices its own lanes' grants (exclusive prefix of the lane
    # axis = this device's window).  The incoming-aware variant only
    # exists under the lane-work cond (matching sharded._tick_impl) --
    grants0 = sharded._alloc_removes_arrays(
        scfg, sizes_pre, min_v, rm_residual, incoming=0, grant_cap=grant_cap
    )
    my_counts = jax.lax.dynamic_slice_in_dim(counts, lane_lo, n_local, 0)
    my_grants0 = jax.lax.dynamic_slice_in_dim(grants0, lane_lo, n_local, 0)

    # -- device-local lane-work hoist: unlike the single-device queue's
    # global any, each device skips on ITS lanes' predicate alone (a
    # mesh neighbor's work is not ours).  Bit-exactness of skip vs run
    # for a no-work lane is the PR-2/PR-3 guarantee pinned by
    # tests/test_tick_repairs.py; a grant can never appear on a lane
    # whose grants0 slice was zero without incoming on that same lane
    # (others' incoming only pushes a lane's head rank back), so the
    # predicate is a sound superset --
    quiet1 = local.quiet_ticks + 1
    my_chop = jnp.any((quiet1 >= lc.chop_patience) & (local.seq_len > 0))
    has_adds = my_counts.sum(dtype=_I32) > 0
    has_grants = my_grants0.sum(dtype=_I32) > 0
    lane_work = has_adds | has_grants | my_chop

    def _do(lanes_in):
        lk, lv, lm, _ = sharded._route_adds_sorted(
            scfg, route_inv, add_keys, add_vals, add_mask, rows=(lane_lo, n_local)
        )
        grants = sharded._alloc_removes_arrays(
            scfg, sizes_pre, min_v, rm_residual, incoming=incoming, grant_cap=grant_cap
        )
        my_grants = jax.lax.dynamic_slice_in_dim(grants, lane_lo, n_local, 0)
        lanes2, res, n_lane = sharded._lanes_tick(
            lc, lanes_in, lk, lv, lm, my_grants, adds_sorted=True
        )
        return lanes2, res.rm_keys, res.rm_vals, n_lane

    def _skip(lanes_in):
        st = lanes_in.stats
        lanes2 = lanes_in._replace(
            quiet_ticks=quiet1, stats=st._replace(n_ticks=st.n_ticks + 1)
        )
        return (
            lanes2,
            jnp.full((n_local, rl), INF, _F32),
            jnp.full((n_local, rl), EMPTY_VAL, _I32),
            jnp.zeros((n_local,), _I32),
        )

    lanes2, res_k, res_v, n_lane = jax.lax.cond(lane_work, _do, _skip, local)

    new_state = ShardedState(
        lanes=lanes2,
        rng=key,
        route=route,
        route_inv=route_inv,
        tick_idx=state.tick_idx + 1,
        n_router_dropped=state.n_router_dropped + n_drop,
        elim_ema=elim_ema,
        balance_ema=balance_ema,
        disp_ema=disp_ema,
        n_preroute_elim=state.n_preroute_elim + n_matched,
        n_preroute_ticks=state.n_preroute_ticks + elim_ran.astype(_I32),
    )
    return new_state, (matched_k, matched_v, n_matched, res_k, res_v, n_lane)


def _make_mapped(cfg: DistShardedPQConfig, mesh: Mesh):
    body = functools.partial(
        _dist_tick_body, cfg.shard, cfg.lanes_per_device, cfg.axis
    )
    sspec = _state_specs(cfg.axis)
    lane_res = (P(), P(), P(), P(cfg.axis), P(cfg.axis), P(cfg.axis))
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(sspec, P(), P(), P(), P(), P()),
        out_specs=(sspec, lane_res),
    )


def make_dist_tick(cfg: DistShardedPQConfig, mesh: Mesh):
    """Jitted one-round tick over the mesh; same signature and result
    type as ``sharded.tick`` (state is DONATED)."""
    mapped = _make_mapped(cfg, mesh)

    @functools.partial(jax.jit, donate_argnums=0)
    def dist_tick(
        state: ShardedState, add_keys, add_vals, add_mask, rm_count, lane_scale
    ) -> Tuple[ShardedState, ShardedTickResult]:
        new_state, parts = mapped(
            state,
            add_keys,
            add_vals,
            add_mask,
            jnp.asarray(rm_count, _I32),
            jnp.asarray(lane_scale, _F32),
        )
        mk, mv, nm, rk, rv, nl = parts
        return new_state, sharded._fold_results(nm, mk, mv, rk, rv, nl)

    return dist_tick


def make_dist_tick_n(cfg: DistShardedPQConfig, mesh: Mesh):
    """`lax.scan` multi-tick driver over [T, ...]-stacked op batches
    (one dispatch for T synchronized rounds; state is DONATED) — the
    bench driver, mirroring ``sharded.tick_n``."""
    mapped = _make_mapped(cfg, mesh)

    @functools.partial(jax.jit, donate_argnums=0)
    def dist_tick_n(
        state: ShardedState, add_keys, add_vals, add_mask, rm_counts, lane_scale
    ):
        scale = jnp.asarray(lane_scale, _F32)

        def step(s, xs):
            ak, av, am, rm = xs
            s2, parts = mapped(s, ak, av, am, rm, scale)
            mk, mv, nm, rk, rv, nl = parts
            return s2, sharded._fold_results(nm, mk, mv, rk, rv, nl)

        xs = (add_keys, add_vals, add_mask, jnp.asarray(rm_counts, _I32))
        return jax.lax.scan(step, state, xs)

    return dist_tick_n


# ---------------------------------------------------------------------------
# elastic resize (drain-and-remap a dead device's lanes over survivors)
# ---------------------------------------------------------------------------


def resize(
    cfg: DistShardedPQConfig,
    mesh: Mesh,
    state: ShardedState,
    drop_device: int,
) -> Tuple[DistShardedPQConfig, Mesh, ShardedState, np.ndarray, np.ndarray]:
    """Shrink the mesh by one device: D·l lanes -> (D−1)·l.

    Host-level (eager, rare path — runs once per death verdict, not per
    tick).  The dropped device's lanes are DRAINED via
    :func:`sharded.fold_lanes` — their resident elements come back as a
    flat (keys, vals) batch for the caller to re-add through ordinary
    ticks on the survivor mesh (the re-derived permuted round-robin
    remaps them; :meth:`DistShardedQueue.remove_device` does both
    halves).  Survivor lanes carry bit-for-bit; the replicated control
    plane (PRNG, route, inverse) is re-derived for the new L, exactly
    as a single-device fold.

    Returns ``(new_cfg, new_mesh, new_state, drained_keys,
    drained_vals)`` with ``new_state`` already placed on ``new_mesh``
    (the old mesh minus the dropped position).  Works from the
    coordinator's host copy of the state — in a real multi-host death
    the dead device's HBM is gone, so the drain source would be the
    replicated control plane plus the survivors' checkpoint of the lost
    lanes; the single-host fake-device mesh (CI) reads the leaves
    directly.
    """
    if cfg.n_devices < 2:
        raise ValueError("cannot drop the last device")
    if not 0 <= drop_device < cfg.n_devices:
        raise ValueError(f"drop_device {drop_device} out of range")
    lpd = cfg.lanes_per_device
    lo = drop_device * lpd
    keep = [i for i in range(cfg.shard.n_lanes) if not lo <= i < lo + lpd]
    host = jax.tree.map(np.asarray, state)
    new_scfg, folded, drained_keys, drained_vals = sharded.fold_lanes(
        cfg.shard, host, keep
    )
    new_cfg = DistShardedPQConfig(
        shard=new_scfg, n_devices=cfg.n_devices - 1, axis=cfg.axis
    )
    devs = list(np.asarray(mesh.devices).reshape(-1))
    del devs[drop_device]
    new_mesh = Mesh(np.asarray(devs), (cfg.axis,))
    new_state = jax.device_put(folded, _placement(new_cfg, new_mesh))
    return new_cfg, new_mesh, new_state, drained_keys, drained_vals


def reinsert(
    q: "DistShardedQueue", state: ShardedState, keys: np.ndarray, vals: np.ndarray
) -> ShardedState:
    """Re-add a drained batch through ordinary rm_count=0 ticks (the
    remap half of drain-and-remap).

    A zero-remove tick provably serves nothing (elimination opportunity
    = min(adds, 0) = 0, grants = 0), so re-insertion cannot lose or
    reorder anything — it only places.  Chunking keeps the router
    drop-free: full batch width when the survivor quota covers it
    (``spare_devices`` sizing), else ``lane.a_max`` per round (a chunk
    no lane can overflow on, whatever the permutation does).
    """
    scfg = q.cfg.shard
    w = scfg.a_total
    if -(-w // scfg.n_lanes) <= scfg.lane.a_max:
        chunk = w
    else:
        chunk = scfg.lane.a_max
    dropped_pre = int(state.n_router_dropped)
    for i in range(0, len(keys), chunk):
        ck = np.asarray(keys[i : i + chunk], np.float32)
        cv = np.asarray(vals[i : i + chunk], np.int32)
        ak = np.full((w,), np.inf, np.float32)
        av = np.full((w,), EMPTY_VAL, np.int32)
        m = np.zeros((w,), bool)
        ak[: len(ck)] = ck
        av[: len(cv)] = cv
        m[: len(ck)] = True
        state, _ = q.tick(
            state,
            jnp.asarray(ak),
            jnp.asarray(av),
            jnp.asarray(m),
            jnp.zeros((), _I32),
        )
    dropped = int(state.n_router_dropped) - dropped_pre
    if dropped:
        raise AssertionError(
            f"re-insertion dropped {dropped} keys — survivor lane quotas "
            "under-sized (EngineSpec spare_devices) and chunking failed"
        )
    return state


class DistShardedQueue:
    """Lanes-over-devices sharded queue (module docstring has the
    design; DESIGN.md §3.4 the cost model).

    Bundles a config, a mesh, and the jitted tick/tick_n closures; the
    state stays explicit and flows through ``tick`` functionally, like
    every other queue in the repo::

        q = make_engine(EngineSpec(engine="dist", width=256, lanes=16,
                                   n_devices=8, lanes_per_device=2,
                                   base=cfg))
        state = q.init(seed=0)
        state, res = q.tick(state, keys, vals, mask, rm_count)

    ``tick`` donates ``state``; results are near-minimal key sets under
    ``q.relax_bound(rm_count)`` with L = D * l, exactly as single-device
    ``sharded`` — the two serve the same stream on the same ops.
    """

    kind = "dist"

    def __init__(self, cfg: DistShardedPQConfig, mesh: Optional[Mesh] = None):
        if mesh is None:
            mesh = default_mesh(cfg)
        if mesh.shape[cfg.axis] != cfg.n_devices:
            raise ValueError(
                f"mesh axis {cfg.axis!r} has {mesh.shape[cfg.axis]} "
                f"devices, config wants {cfg.n_devices}"
            )
        self.cfg = cfg
        self.mesh = mesh
        self._tick = make_dist_tick(cfg, mesh)
        self._tick_n = make_dist_tick_n(cfg, mesh)
        # all-ones = unthrottled (bit-identical to a capless allocation)
        self._no_scale = jnp.ones((cfg.shard.n_lanes,), _F32)

    def init(self, *, seed: int = 0) -> ShardedState:
        return init(self.cfg, self.mesh, seed=seed)

    def tick(
        self,
        state: ShardedState,
        add_keys,
        add_vals,
        add_mask,
        rm_count,
        lane_scale=None,
    ) -> Tuple[ShardedState, ShardedTickResult]:
        if lane_scale is None:
            lane_scale = self._no_scale
        with obs.span(obs.SPAN_TICK):
            return self._tick(
                state, add_keys, add_vals, add_mask, rm_count, lane_scale
            )

    def tick_n(
        self,
        state: ShardedState,
        add_keys,
        add_vals,
        add_mask,
        rm_counts,
        lane_scale=None,
    ) -> Tuple[ShardedState, ShardedTickResult]:
        if lane_scale is None:
            lane_scale = self._no_scale
        with obs.span(obs.SPAN_TICK_N):
            return self._tick_n(
                state, add_keys, add_vals, add_mask, rm_counts, lane_scale
            )

    def remove_device(
        self, state: ShardedState, device: int, *, reinsert_drained: bool = True
    ) -> Tuple["DistShardedQueue", ShardedState]:
        """Drain-and-remap ``device``'s lanes over the survivors.

        Returns ``(new_queue, new_state)`` — a fresh
        :class:`DistShardedQueue` over the (D−1)-device mesh with the
        dead device's resident elements re-inserted (unless
        ``reinsert_drained=False``, for callers that stage the re-add
        themselves).  Multiset conservation across the resize and the
        ``relax_bound`` contract at the new L from the first post-resize
        tick are pinned by tests/test_dist_resize.py.
        """
        new_cfg, new_mesh, new_state, dk, dv = resize(
            self.cfg, self.mesh, state, device
        )
        q2 = DistShardedQueue(new_cfg, new_mesh)
        if reinsert_drained:
            new_state = reinsert(q2, new_state, dk, dv)
        return q2, new_state

    def stats(self, state: ShardedState) -> sharded.ShardedStats:
        return sharded.stats(state)

    def resident(self, state: ShardedState):
        """(keys, vals, live) of every resident element — the
        :class:`~repro.core.factory.QueueEngine` drain surface."""
        return sharded.resident(self.cfg.shard, state.lanes)

    @property
    def width(self) -> int:
        return self.cfg.shard.a_total

    def size(self, state: ShardedState) -> jnp.ndarray:
        return sharded.size(state)

    def lane_sizes(self, state: ShardedState) -> jnp.ndarray:
        return sharded.lane_sizes(state)

    def relax_bound(self, rm_count: int) -> int:
        return sharded.relax_bound(self.cfg.shard, rm_count)
