"""Factory + protocol tests: one spec resolves every engine, the legacy
constructors are deprecation-only, and no in-repo caller still uses them.
"""

import pathlib
import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import PQConfig
from repro.core import sharded as shq
from repro.core.config import EMPTY_VAL
from repro.core.factory import (
    EngineSpec,
    QueueEngine,
    default_base,
    engine_kinds,
    make_engine,
    resolved_base,
)

W = 64
BASE = PQConfig(a_max=W, r_max=W, seq_cap=512, n_buckets=16, bucket_cap=32,
                detach_min=4, detach_max=64, detach_init=8, chop_patience=8)


def _spec(engine, **kw):
    return EngineSpec(engine=engine, width=W, base=BASE, **kw)


# ---------------------------------------------------------------------------
# registry resolution
# ---------------------------------------------------------------------------

def test_registry_lists_every_kind():
    kinds = engine_kinds()
    for k in ("pqe", "sharded", "dist", "elastic", "adaptive",
              "fcskiplist", "lfskiplist"):
        assert k in kinds, kinds


def test_unknown_engine_raises_with_inventory():
    with pytest.raises(ValueError, match="unknown engine 'skiplist'"):
        make_engine(EngineSpec(engine="skiplist"))


@pytest.mark.parametrize("engine", ["pqe", "sharded", "adaptive",
                                    "fcskiplist", "lfskiplist"])
def test_single_device_kinds_build_and_tick(engine):
    eng = make_engine(_spec(engine, lanes=4))
    assert eng.kind == engine
    assert eng.width == W
    state = eng.init(seed=0)
    ak = jnp.asarray(np.linspace(1.0, 64.0, W, dtype=np.float32))
    av = jnp.arange(W, dtype=jnp.int32)
    m = jnp.ones((W,), bool)
    state, _ = eng.tick(state, ak, av, m, jnp.asarray(0))
    state, res = eng.tick(state, jnp.full((W,), jnp.inf, jnp.float32),
                          jnp.full((W,), EMPTY_VAL, jnp.int32),
                          jnp.zeros((W,), bool), jnp.asarray(8))
    served = np.asarray(res.rm_keys)[np.asarray(res.rm_served)]
    assert len(served) == 8
    # every engine's removes honor its own declared relaxation bound
    cut = min(eng.relax_bound(8), W) - 1
    assert served.max() <= np.sort(np.linspace(1, 64, W))[cut]


def test_dist_kind_builds_on_one_device():
    eng = make_engine(_spec("dist", lanes=4, n_devices=1))
    assert eng.kind == "dist" and eng.width == W
    state = eng.init(seed=0)
    assert int(eng.size(state)) == 0


def test_dist_lanes_must_divide_devices():
    with pytest.raises(ValueError, match="divide evenly"):
        make_engine(_spec("dist", lanes=3, n_devices=2))


def test_builder_kwargs_pass_through_and_unknown_raise():
    with pytest.raises(TypeError):
        make_engine(_spec("pqe"), schedule="nope")


# ---------------------------------------------------------------------------
# the QueueEngine protocol
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["pqe", "sharded", "adaptive"])
def test_engines_satisfy_protocol(engine):
    eng = make_engine(_spec(engine, lanes=4))
    assert isinstance(eng, QueueEngine)
    for name in ("init", "tick", "tick_n", "stats", "resident",
                 "relax_bound", "size", "width", "kind"):
        assert hasattr(eng, name), name


def test_relax_bounds_per_engine():
    assert make_engine(_spec("pqe")).relax_bound(8) == 8   # exact queue
    sb = make_engine(_spec("sharded", lanes=4)).relax_bound(8)
    assert sb == shq.relax_bound(make_engine(_spec("sharded", lanes=4)).cfg, 8)
    assert sb > 8
    # adaptive must quote its loosest candidate: the full-L sharded bound
    assert make_engine(_spec("adaptive", lanes=4)).relax_bound(8) == sb


# ---------------------------------------------------------------------------
# spec resolution
# ---------------------------------------------------------------------------

def test_default_base_when_unset():
    spec = EngineSpec(engine="pqe", width=128)
    assert resolved_base(spec) == default_base(128)
    assert resolved_base(spec).a_max == 128


def test_detach_knobs_override_base():
    eng = make_engine(_spec("pqe", detach_init=16, detach_max=32,
                            halve_threshold=500))
    assert eng.cfg.detach_init == 16
    assert eng.cfg.detach_max == 32
    assert eng.cfg.halve_threshold == 500
    assert eng.cfg.detach_min == BASE.detach_min   # untouched knob carries
    # the caller's base config object is not mutated
    assert BASE.detach_init == 8


def test_sharded_spec_matches_legacy_cfg():
    got = make_engine(_spec("sharded", lanes=8, preroute="off")).cfg
    want = shq._sharded_cfg(W, 8, base=BASE, preroute="off")
    assert got == want


# ---------------------------------------------------------------------------
# backend selection flows through the spec (the api_redesign contract)
# ---------------------------------------------------------------------------

def test_backend_resolves_once_into_config():
    from repro.kernels.ops import KernelBackend

    eng = make_engine(_spec("pqe", backend="pallas_interpret"))
    assert eng.cfg.backend == KernelBackend("pallas", interpret=True)
    # sharded: the backend must reach the LANE config the tick dispatches
    # on, not just the wrapper
    sh = make_engine(_spec("sharded", lanes=4, backend="jnp"))
    assert sh.cfg.lane.backend == KernelBackend("jnp")
    # already-resolved objects pass through untouched
    bk = KernelBackend("pallas", interpret=True)
    assert make_engine(_spec("pqe", backend=bk)).cfg.backend is bk


def test_backend_unset_keeps_base_config_backend():
    import dataclasses
    from repro.kernels.ops import KernelBackend

    base = dataclasses.replace(BASE, backend="pallas_interpret")
    eng = make_engine(EngineSpec(engine="pqe", width=W, base=base))
    assert eng.cfg.backend == KernelBackend("pallas", interpret=True)


def test_invalid_backend_raises_at_construction():
    with pytest.raises(ValueError, match="unknown kernel backend"):
        make_engine(_spec("pqe", backend="cuda"))
    with pytest.raises(ValueError, match="unknown kernel backend"):
        PQConfig(a_max=W, r_max=W, backend="tpu")


def test_pqconfig_canonicalizes_backend_string():
    from repro.kernels.ops import KernelBackend

    cfg = PQConfig(a_max=W, r_max=W, backend="pallas_interpret")
    assert cfg.backend == KernelBackend("pallas", interpret=True)
    # the default spelling is resolved at construction too
    assert PQConfig(a_max=W, r_max=W).backend == KernelBackend("jnp")


def test_auto_resolves_to_jnp_on_every_platform(monkeypatch):
    """No Pallas kernel compiles for v5e (tests/test_tpu_compile.py), so
    "auto" — and the ops' default — is the jnp tick even on a TPU."""
    from repro.kernels import ops

    monkeypatch.delenv("PQ_BACKEND", raising=False)
    for platform in ("cpu", "tpu"):
        monkeypatch.setattr(ops.jax, "default_backend", lambda: platform)
        assert ops.resolve_backend("auto") == ops.KernelBackend("jnp")
        assert ops.resolve_backend(None) == ops.KernelBackend("jnp")
        assert ops._coerce(None) == ops.KernelBackend("jnp")
    # on a TPU an explicit "pallas" still asks Mosaic for the kernels
    assert ops.resolve_backend("pallas") == ops.KernelBackend("pallas")


def test_pq_backend_env_still_steers_auto(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setenv("PQ_BACKEND", "pallas_interpret")
    assert ops.resolve_backend("auto") == ops.KernelBackend(
        "pallas", interpret=True)
    monkeypatch.setenv("PQ_BACKEND", "jnp")
    assert ops.resolve_backend("auto") == ops.KernelBackend("jnp")
    monkeypatch.setenv("PQ_BACKEND", "auto")
    with pytest.raises(ValueError, match="PQ_BACKEND"):
        ops.resolve_backend("auto")


def test_pallas_raises_off_tpu(monkeypatch):
    """Interpret mode is asked for by name, never reached by fallback."""
    from repro.kernels import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: "cpu")
    with pytest.raises(ValueError, match="needs a TPU"):
        ops.resolve_backend("pallas")
    with pytest.raises(ValueError, match="needs a TPU"):
        make_engine(_spec("pqe", backend="pallas"))
    with pytest.raises(ValueError, match="needs a TPU"):
        PQConfig(a_max=W, r_max=W, backend="pallas")


@pytest.mark.parametrize("kernel", ["bitonic_sort_kvf", "merge_sorted_kvf",
                                    "radix_select_threshold"])
def test_kernel_entry_points_take_interpret_explicitly(kernel):
    """No kernel entry point defaults ``interpret``: a caller picks
    Mosaic or the interpreter, never inherits one."""
    import inspect

    from repro.kernels import bitonic, merge_consume, radix_select

    mods = {"bitonic_sort_kvf": bitonic, "merge_sorted_kvf": merge_consume,
            "radix_select_threshold": radix_select}
    fn = inspect.unwrap(getattr(mods[kernel], kernel))
    param = inspect.signature(fn).parameters["interpret"]
    assert param.default is inspect.Parameter.empty
    assert param.kind is inspect.Parameter.KEYWORD_ONLY


def test_no_per_call_backend_strings():
    """Backend selection is config-only: no in-repo call site may pass a
    backend="..." STRING to a kernel op (the deprecated per-call alias).
    Textual scan like the legacy-constructor gate above, so a regressed
    site fails CI even if nothing imports it.  src/repro/kernels/ is
    exempt (the dispatch layer itself); config-level backend= kwargs
    (PQConfig/EngineSpec) do not match — only op-call windows do."""
    import re

    ops_call = re.compile(
        r"(?:sort_kvf|merge_sorted|select_threshold|select_k_smallest"
        r"|extract_k_bucketed|searchsorted_last)\s*\(")
    per_call = re.compile(r"backend\s*=\s*[\"']")
    root = pathlib.Path(__file__).resolve().parents[1]
    kernels_dir = root / "src" / "repro" / "kernels"
    offenders = []
    for sub in ("src", "tests", "benchmarks", "scripts", "examples"):
        for path in sorted((root / sub).rglob("*.py")):
            if kernels_dir in path.parents or path == pathlib.Path(
                    __file__).resolve():
                continue
            text = path.read_text()
            for m in ops_call.finditer(text):
                # span to the call's closing paren (naive depth count is
                # fine: op calls never nest another op call in-args)
                depth, i = 1, m.end()
                while i < len(text) and depth:
                    depth += {"(": 1, ")": -1}.get(text[i], 0)
                    i += 1
                if per_call.search(text[m.start():i]):
                    line = text.count("\n", 0, m.start()) + 1
                    offenders.append(f"{path.relative_to(root)}:{line}")
    assert not offenders, (
        "per-call backend= strings remain (set backend on "
        f"PQConfig/EngineSpec instead): {offenders}")


# ---------------------------------------------------------------------------
# deprecation of the legacy constructors
# ---------------------------------------------------------------------------

def test_make_sharded_cfg_is_deprecated_but_equivalent():
    with pytest.deprecated_call():
        old = shq.make_sharded_cfg(W, 4, base=BASE)
    assert old == make_engine(_spec("sharded", lanes=4)).cfg


def test_make_dist_cfg_is_deprecated():
    from repro.core import distributed as dq

    with pytest.deprecated_call():
        cfg = dq.make_dist_cfg(W, 1, 4, base=BASE)
    assert cfg.shard.n_lanes == 4


def test_no_in_repo_caller_uses_legacy_constructors():
    """The deprecated names survive exactly one PR as aliases; every
    in-repo construction must already go through make_engine.  Scans the
    source tree textually so a regressed call site fails CI even if no
    test imports it."""
    root = pathlib.Path(__file__).resolve().parents[1]
    allowed = {
        root / "src" / "repro" / "core" / "sharded.py",      # definition
        root / "src" / "repro" / "core" / "distributed.py",  # definition
        pathlib.Path(__file__).resolve(),                    # this test
    }
    offenders = []
    for sub in ("src", "tests", "benchmarks", "scripts", "examples"):
        for path in sorted((root / sub).rglob("*.py")):
            if path in allowed:
                continue
            text = path.read_text()
            for name in ("make_sharded_cfg(", "make_dist_cfg("):
                if name in text:
                    offenders.append(f"{path.relative_to(root)}: {name}")
    assert not offenders, (
        "legacy constructor call sites remain (use "
        f"repro.core.factory.make_engine): {offenders}")


def test_deprecated_aliases_warn_exactly_once_per_call():
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        shq.make_sharded_cfg(W, 2, base=BASE)
    assert sum(issubclass(w.category, DeprecationWarning) for w in rec) == 1
