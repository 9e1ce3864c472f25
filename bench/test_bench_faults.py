"""``correct`` comes out false when the timed path is broken underneath,
and for the control; the whole run is driven, only the look for a chip
is skipped.

The four-device faults run in a child process with four host devices
(the device count is fixed when JAX starts).
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness, tiny

ROOT = Path(__file__).resolve().parents[1]


class _Wrapped:
    """The real engine with one thing broken."""

    def __init__(self, inner):
        self.inner = inner
        self.width = inner.width

    def init(self, *, seed=0):
        return self.inner.init(seed=seed)

    def stats(self, state):
        return self.inner.stats(state)

    def resident(self, state):
        return self.inner.resident(state)

    def size(self, state):
        return self.inner.size(state)


class StateUnchanged(_Wrapped):
    """A step that returns its state unchanged."""

    def tick_n(self, state, keys, vals, mask, rm):
        copy = jax.tree.map(jnp.copy, state)
        _, res = self.inner.tick_n(copy, keys, vals, mask, rm)
        return state, res


class HalfBatch(_Wrapped):
    """Half of each batch's adds left out."""

    def tick_n(self, state, keys, vals, mask, rm):
        mask = mask.copy()
        live = np.flatnonzero(mask[0])
        mask[0, live[live.size // 2:]] = False
        return self.inner.tick_n(state, keys, vals, mask, rm)


class AnswerAltered(_Wrapped):
    """One served key altered where it is produced."""

    def tick_n(self, state, keys, vals, mask, rm):
        state, res = self.inner.tick_n(state, keys, vals, mask, rm)
        i = jnp.argmax(res.rm_served[0])
        k = res.rm_keys.at[0, i].set(jnp.nextafter(res.rm_keys[0, i],
                                                   jnp.inf))
        return state, res._replace(rm_keys=k)


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    d = tmp_path_factory.mktemp("bench")
    return d, tiny.bench_tree(d)


def _run(tree, cell, make):
    d, bench = tree
    return harness.run_cell(bench, cell, seed=2**31 + 1, seconds=0.2,
                            traced=False, devices=jax.devices(),
                            bench_dir=d, make=make, emit=lambda s: None)


def _broken(fault):
    return lambda config, devices: fault(harness.build_engine(config, devices))


@pytest.mark.parametrize("cell", ["tiny_exact.hold", "tiny_relaxed.uniform"])
@pytest.mark.parametrize("fault,fails", [
    (StateUnchanged, "resident_gap"),
    (HalfBatch, "resident_gap"),
    (AnswerAltered, "bad_pairs"),
], ids=["state_unchanged", "half_batch", "answer_altered"])
def test_fault_makes_the_run_incorrect(tree, cell, fault, fails):
    r = _run(tree, cell, _broken(fault))
    assert r["correct"] is False
    assert r["checks"][fails]["value"] > r["checks"][fails]["limit"]
    assert r["failed"] > 0


@pytest.mark.parametrize("cell", ["tiny_exact.hold", "tiny_exact.uniform",
                                  "tiny_relaxed.uniform"])
def test_control_in_bfloat16_is_incorrect(tree, cell):
    r = _run(tree, cell, control.Bf16Queue)
    assert r["correct"] is False
    assert r["checks"]["bad_pairs"]["value"] > 0
    assert r["checks"]["count_gap"]["value"] == 0      # it serves every remove


def test_control_in_float32_would_pass(tree, monkeypatch):
    """The control differs from a sound queue by its precision alone."""
    monkeypatch.setattr(jnp, "bfloat16", jnp.float32)
    r = _run(tree, "tiny_exact.uniform", control.Bf16Queue)
    assert r["correct"] is True, r["checks"]


_DIST_CHILD = r"""
import sys, json
sys.path[:0] = [{root!r}, {src!r}]
import jax, jax.numpy as jnp, tempfile
from bench import harness, tiny

def no_exchange(x, axis_name, **kw):
    # each chip receives nothing from the others: their entries stay empty
    fill = jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else 0
    out = jnp.full((4,) + x.shape, fill, x.dtype)
    return out.at[jax.lax.axis_index(axis_name)].set(x)

d = tempfile.mkdtemp()
bench = tiny.bench_tree(d)
out = {{}}
for name in ("sound", "no_exchange"):
    if name == "no_exchange":
        jax.lax.all_gather = no_exchange
    r = harness.run_cell(bench, "tiny_dist.uniform", seed=7, seconds=0.3,
                         traced=False, devices=jax.devices(), bench_dir=d,
                         emit=lambda s: None)
    out[name] = {{"correct": r["correct"], "checks": r["checks"]}}
print(json.dumps(out))
"""


def test_dist_cell_sound_and_without_the_exchange():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _DIST_CHILD.format(root=str(ROOT), src=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    import json

    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"] is True, out["sound"]
    assert out["no_exchange"]["correct"] is False
    assert out["no_exchange"]["checks"]["count_gap"]["value"] > 0
