"""Logical-axis sharding: named activation axes resolved against a mesh.

Model code annotates activations with *logical* axis names ("batch",
"seq", "vocab", ...) instead of mesh axes; a rule table maps logical →
physical per topology, so the same model runs unsharded (no mesh), on a
2-D (data, model) pod slice, or on a 3-D (pod, data, model) multi-pod
mesh.  ``use_mesh`` installs the (mesh, rules) pair in a context; outside
any mesh every annotation is a no-op, which is what keeps single-device
tests and CPU benches mesh-free.

Divisibility: GSPMD requires each sharded dim to divide by the axis size;
``shard``/``spec`` silently drop a physical axis that does not divide
(matching ``launch.train.sanitize_spec``), so annotations are safe on
reduced test configs (e.g. vocab=512 on a 16-way model axis).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical -> tuple of physical mesh axes (applied in order, outermost
# first).  "seq" is unsharded by default; sp_rules() flips it to "model"
# (sequence parallelism: the residual stream shards over S between
# attention/MLP blocks).
RULES_2D: Dict[str, Tuple[str, ...]] = {
    "batch": ("data",),
    "seq": (),
    "model": ("model",),
    "vocab": ("model",),
    "heads": ("model",),
    "expert": ("model",),
}

RULES_3D: Dict[str, Tuple[str, ...]] = {
    **RULES_2D,
    "batch": ("pod", "data"),
}


def sp_rules(base: Dict[str, Tuple[str, ...]]) -> Dict[str, Tuple[str, ...]]:
    """Sequence-parallel variant: activations shard over `model` along S."""
    return {**base, "seq": ("model",)}


def shard_map(body, *, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: the check rejects
    the manual psum patterns the distributed tick uses."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis typed Auto."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Dict[str, Tuple[str, ...]] = RULES_2D


_CTX = _Ctx()


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def current_rules() -> Dict[str, Tuple[str, ...]]:
    return _CTX.rules


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: Optional[Dict[str, Tuple[str, ...]]] = None):
    """Install (mesh, rules) for the dynamic extent; nestable."""
    if rules is None:
        rules = RULES_3D if "pod" in mesh.axis_names else RULES_2D
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, rules
    try:
        with mesh:
            yield mesh
    finally:
        _CTX.mesh, _CTX.rules = prev


def _resolve(axis, mesh: Mesh) -> Tuple[str, ...]:
    """Logical name -> physical axes present on this mesh."""
    if axis is None:
        return ()
    names = _CTX.rules.get(axis, ())
    return tuple(a for a in names if a in mesh.axis_names)


def spec(*logical) -> P:
    """PartitionSpec for logical axis names under the active rules.

    Unknown names and names whose physical axes are absent from the mesh
    resolve to None (replicated).  Without an active mesh, returns a fully
    replicated spec (same arity).
    """
    mesh = _CTX.mesh
    if mesh is None:
        return P(*([None] * len(logical)))
    parts = []
    for ax in logical:
        phys = _resolve(ax, mesh)
        parts.append(phys if len(phys) > 1 else (phys[0] if phys else None))
    return P(*parts)


def shard(x, *logical):
    """with_sharding_constraint by logical names; no-op without a mesh.

    Trailing dims may be omitted (replicated).  Physical axes that do not
    divide the dim are dropped rather than erroring.
    """
    mesh = _CTX.mesh
    if mesh is None:
        return x
    ndim = x.ndim
    names = list(logical) + [None] * (ndim - len(logical))
    parts = []
    for ax, n in zip(names, x.shape):
        keep = []
        prod = 1
        for a in _resolve(ax, mesh):
            if n % (prod * mesh.shape[a]) == 0:
                keep.append(a)
                prod *= mesh.shape[a]
        parts.append(tuple(keep) if len(keep) > 1 else
                     (keep[0] if keep else None))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*parts)))


def shard_activation_sp(x):
    """Sequence-parallel residual constraint for [B, S, D] activations."""
    return shard(x, "batch", "seq", None)
