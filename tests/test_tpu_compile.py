"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

The TPU compiler is installed with libtpu, so a program can be compiled
for a ``v5e:2x2`` topology that is described, not attached: what Mosaic
or XLA:TPU would refuse on the chip, they refuse here.  Nothing runs.

* The jnp ``tick_n`` — the chip path — compiles at deployment size for
  ``pqe`` and ``sharded`` on one chip and for ``dist`` over four, with
  the shapes chip_smoke.py runs.  ``memory_analysis()`` is printed and
  checked against the chip's 16 GB.
* Every Pallas program is refused today.  Each refusal is a strict
  xfail with the compiler's reason: the change that makes a kernel
  compile turns its case into an unexpected pass, which fails until the
  marker is removed.

The topology is described inside a module fixture, never at import: the
TPU library admits one process at a time, and test workers import every
test file.  The persistent compilation cache is off for these compiles
(an entry compiled for a described chip cannot be read back without one).
"""

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import pqueue
from repro.core import sharded as shq
from repro.core.config import PRODUCTION
from repro.core.factory import EngineSpec, make_engine
from repro.kernels.ops import KernelBackend

#: ticks per compiled tick_n call (chip_smoke.CHUNK)
TICKS = 50
#: HBM of one v5e chip
V5E_HBM = 16 * 10**9
#: a Pallas backend with Mosaic, steered here without a chip attached
MOSAIC = KernelBackend("pallas")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it is held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices[:4]), ("data",))


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _batches(width, sharding):
    """[TICKS, W] add keys / vals / mask and [TICKS] remove counts."""
    return (jax.ShapeDtypeStruct((TICKS, width), jnp.float32,
                                 sharding=sharding),
            jax.ShapeDtypeStruct((TICKS, width), jnp.int32,
                                 sharding=sharding),
            jax.ShapeDtypeStruct((TICKS, width), jnp.bool_,
                                 sharding=sharding),
            jax.ShapeDtypeStruct((TICKS,), jnp.int32, sharding=sharding))


def _fits(name, compiled):
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"{name}: args={mem.argument_size_in_bytes} "
          f"temp={mem.temp_size_in_bytes} out={mem.output_size_in_bytes}")
    assert used < V5E_HBM, f"{name} needs {used} bytes: {mem}"


def _pqe_tick_n(cfg, sharding):
    state = _shapes(jax.eval_shape(lambda: pqueue.init(cfg)), sharding)
    return pqueue.tick_n.lower(cfg, state,
                               *_batches(cfg.a_max, sharding)).compile()


def _sharded_tick_n(cfg, sharding):
    state = _shapes(jax.eval_shape(lambda: shq.init(cfg, seed=0)),
                    sharding)
    return shq.tick_n.lower(cfg, state,
                            *_batches(cfg.a_total, sharding)).compile()


# ---------------------------------------------------------------------------
# the jnp tick: the chip path, at chip_smoke.py's shapes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pqe_production(one_chip):
    """The pqe chip path at PRODUCTION geometry, compiled once."""
    cfg = make_engine(EngineSpec(engine="pqe", width=1024,
                                 base=PRODUCTION)).cfg
    assert not cfg.backend.is_pallas
    return cfg, _pqe_tick_n(cfg, one_chip)


def test_pqe_tick_n_compiles_at_production(pqe_production):
    _fits("pqe", pqe_production[1])


_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?(%\S+) = (.*?) ([a-z][\w-]*)\((.*)$")


def _elems(shape: str) -> int:
    """Elements of the largest array in an HLO shape (tuples included)."""
    return max((math.prod(int(d) for d in dims.split(",") if d)
                for dims in re.findall(r"[a-z]\w*\[([\d,]*)\]", shape)),
               default=0)


def _wide_ops_in_scope(hlo_text: str, scope: str, min_elems: int):
    """(op, name, elements) of each gather whose operand, and each while
    whose state, holds an array of min_elems or more, in `scope`."""
    shapes, found = {}, []
    for line in hlo_text.splitlines():
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        name, shape, op, rest = m.groups()
        shapes[name] = shape
        if op not in ("gather", "while") or f"/{scope}/" not in rest:
            continue
        wide = (_elems(shapes.get(rest.split(",")[0].strip(), ""))
                if op == "gather" else _elems(shape))
        if wide >= min_elems:
            found.append((op, name, wide))
    return found


def test_pqe_combine_has_no_wide_gather_or_loop(pqe_production):
    """The combine pass at PRODUCTION merges and cuts its windows with
    compares, static shifts and dynamic slices: no gather over the
    seq_cap-wide head or merged stream, and no searchsorted loop."""
    cfg, compiled = pqe_production
    text = compiled.as_text()
    assert "/pq.combine/" in text
    assert _wide_ops_in_scope(text, "pq.combine", cfg.seq_cap) == []


def test_wide_op_guard_sees_a_gather_and_a_search_loop(one_chip):
    """The guard above finds what it looks for: a take_along_axis and a
    searchsorted under the scope, compiled for the chip."""
    @jax.jit
    def f(x, i):
        with jax.named_scope("pq.combine"):
            return (jnp.take_along_axis(x, i, axis=-1),
                    jnp.searchsorted(x, x[::2]))

    x = jax.ShapeDtypeStruct((4096,), jnp.float32, sharding=one_chip)
    i = jax.ShapeDtypeStruct((4096,), jnp.int32, sharding=one_chip)
    found = _wide_ops_in_scope(f.lower(x, i).compile().as_text(),
                               "pq.combine", 4096)
    assert {op for op, _, _ in found} == {"gather", "while"}, found


def test_sharded_l8_tick_n_compiles_at_w8192(one_chip):
    cfg = make_engine(EngineSpec(engine="sharded", width=8192, lanes=8,
                                 base=PRODUCTION)).cfg
    assert not cfg.lane.backend.is_pallas
    _fits("sharded_L8", _sharded_tick_n(cfg, one_chip))


def test_dist_d4_tick_n_compiles_on_2x2_mesh(mesh4):
    from repro.core import distributed as dq

    q = make_engine(EngineSpec(engine="dist", width=8192, lanes=8,
                               n_devices=4, lanes_per_device=2,
                               spare_devices=1, base=PRODUCTION), mesh=mesh4)
    lanes = NamedSharding(mesh4, P("data"))
    rep = NamedSharding(mesh4, P())
    shapes = jax.eval_shape(lambda: shq.init(q.cfg.shard, seed=0))
    state = shapes._replace(**{
        f: _shapes(getattr(shapes, f), lanes if f == "lanes" else rep)
        for f in shapes._fields})
    scale = jax.ShapeDtypeStruct((q.cfg.shard.n_lanes,), jnp.float32,
                                 sharding=rep)
    compiled = dq.make_dist_tick_n(q.cfg, mesh4).lower(
        state, *_batches(q.width, rep), scale).compile()
    _fits("dist_D4", compiled)
    # the per-tick lane-summary all_gathers lower to all-reduces on TPU
    assert " all-reduce(" in compiled.as_text()


# ---------------------------------------------------------------------------
# Pallas programs: every one is refused for v5e today
# ---------------------------------------------------------------------------

def _megakernel_sharded_l8(one_chip):
    base = dataclasses.replace(PRODUCTION, backend=MOSAIC)
    cfg = make_engine(EngineSpec(engine="sharded", width=4096, lanes=8,
                                 base=base)).cfg
    return _sharded_tick_n(cfg, one_chip)


def _megakernel_pqe_l1(one_chip):
    return _pqe_tick_n(dataclasses.replace(PRODUCTION, backend=MOSAIC),
                       one_chip)


def _bitonic(one_chip):
    from repro.kernels.bitonic import bitonic_sort_kvf

    k = jax.ShapeDtypeStruct((8, 4096), jnp.float32, sharding=one_chip)
    v = jax.ShapeDtypeStruct((8, 4096), jnp.int32, sharding=one_chip)
    return bitonic_sort_kvf.lower(k, v, v, interpret=False).compile()


def _merge(one_chip):
    from repro.kernels.merge_consume import merge_sorted_kvf

    k = jax.ShapeDtypeStruct((4096,), jnp.float32, sharding=one_chip)
    v = jax.ShapeDtypeStruct((4096,), jnp.int32, sharding=one_chip)
    return merge_sorted_kvf.lower(k, v, v, k, v, v,
                                  interpret=False).compile()


def _radix(one_chip):
    from repro.kernels.radix_select import radix_select_threshold

    keys = jax.ShapeDtypeStruct((16384,), jnp.float32, sharding=one_chip)
    k = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return radix_select_threshold.lower(keys, k, interpret=False).compile()


@pytest.mark.parametrize("build", [
    pytest.param(_megakernel_sharded_l8, id="megakernel_sharded_L8_w4096",
                 marks=pytest.mark.xfail(strict=True, raises=ValueError,
                 reason="Mosaic: lane block (1, 1026) on an (8, 1026) "
                        "array is not (8, 128)-aligned")),
    pytest.param(_megakernel_pqe_l1, id="megakernel_pqe_L1",
                 marks=pytest.mark.xfail(strict=True,
                 raises=NotImplementedError,
                 reason="Mosaic: 'Only 2D gather is supported' "
                        "(take_along_axis in pqueue._shift_left)")),
    pytest.param(_bitonic, id="bitonic_sort_kvf_8x4096",
                 marks=pytest.mark.xfail(strict=True, raises=ValueError,
                 reason="Mosaic: row block (1, 4096) is not "
                        "(8, 128)-aligned")),
    pytest.param(_merge, id="merge_sorted_kvf_4096+4096",
                 marks=pytest.mark.xfail(strict=True,
                 raises=jax.errors.JaxRuntimeError,
                 reason="Mosaic: operand layout T(1024) does not match "
                        "the kernel's T(256)")),
    pytest.param(_radix, id="radix_select_threshold_16384",
                 marks=pytest.mark.xfail(strict=True, raises=ValueError,
                 reason="Mosaic: cannot store scalars to VMEM")),
])
def test_pallas_program_compiles_for_v5e(build, one_chip):
    _fits(build.__name__, build(one_chip))
