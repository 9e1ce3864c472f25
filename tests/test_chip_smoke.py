"""chip_smoke.py at small sizes on the CPU.

The script refuses to run off a TPU, so these tests call its phase
functions directly with small shapes (the same checks run on the chip at
deployment depth), and check that the script itself exits non-zero at
the device check, with no result line.  The four-device phase runs in
tests/test_multidev.py, which can force host devices.
"""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402
from repro.core import PQConfig  # noqa: E402
from repro.core.factory import EngineSpec  # noqa: E402

SMALL_BASE = PQConfig(a_max=64, r_max=64, seq_cap=512, n_buckets=16,
                      bucket_cap=128, detach_min=4, detach_max=64,
                      detach_init=8, chop_patience=8)


@pytest.fixture(scope="module")
def clock():
    return cs.CompileClock()


@pytest.mark.parametrize("spec", [
    EngineSpec(engine="pqe", width=64, base=SMALL_BASE),
    EngineSpec(engine="sharded", width=128, lanes=8, base=SMALL_BASE),
], ids=["pqe", "sharded_L8"])
def test_queue_phase_checks_pass(spec, clock, capsys):
    out = cs.queue_phase(spec.engine, spec, resident=600, mix_ticks=20,
                         chunk=5, seed=3, clock=clock)
    assert out["resident"] == 600     # p_add 0.5: the mix is net zero
    if spec.engine == "pqe":
        assert out["rank_err_max"] == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert line.startswith(f"[{spec.engine}] compile_s=") and "lost=0" in line


def test_serve_phase_partition_is_exact(clock):
    rep = cs.serve_phase(n_ticks=30, warm_ticks=5, seed=1, clock=clock)
    assert rep["served"] + rep["shed"] + rep["expired"] == rep["arrivals"]
    assert rep["served"] > 0


def test_remaining_multiset_and_invented_keys():
    added = np.array([3.0, 1.0, 2.0, 2.0], np.float32)
    left = cs._remaining(added, np.array([2.0, 1.0]))
    np.testing.assert_array_equal(left, [2.0, 3.0])
    with pytest.raises(cs.SmokeFailure, match="never added"):
        cs._remaining(added, np.array([4.0]))
    with pytest.raises(cs.SmokeFailure, match="never added"):
        cs._remaining(added, np.array([1.0, 1.0]))


def test_stream_loads_then_mixes():
    rng = np.random.default_rng(0)
    (keys, vals, mask, rm), n_load = cs._stream(
        64, resident=600, mix_ticks=10, chunk=5, p_add=0.5, rng=rng)
    assert n_load == 10 and keys.shape == (20, 64) == mask.shape
    assert int(mask[:n_load].sum()) == 600 and not rm[:n_load].any()
    assert np.all(rm[n_load:] == 32) and np.all(mask[n_load:].sum(1) == 32)
    assert np.isfinite(keys[mask]).all() and vals.dtype == np.int32


@pytest.mark.parametrize("alone", [False, True], ids=["checkout", "alone"])
def test_script_refuses_without_a_tpu(alone, tmp_path):
    """Off a TPU the script exits non-zero at the device check and
    prints no result — from the checkout, and from a directory holding
    chip_smoke.py and nothing else of the repo."""
    script = ROOT / "chip_smoke.py"
    if alone:
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    proc = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


@pytest.mark.parametrize("case", ["accelerator_parent", "child_failed",
                                  "no_payload"])
def test_bench_children_never_fail_silently(case, monkeypatch):
    """benchmarks/run.py spawns its dist/serve benches as jax children:
    it refuses to from a parent that holds an accelerator, and any
    child failure raises instead of dropping the cells."""
    import jax

    from benchmarks import run

    spawned = []

    def fake_run(cmd, **kw):
        spawned.append(cmd)
        if case == "child_failed":
            return subprocess.CompletedProcess(cmd, 1, "", "boom")
        return subprocess.CompletedProcess(cmd, 0, "dist_x,1.0,y\n", "")

    monkeypatch.setattr(subprocess, "run", fake_run)
    if case == "accelerator_parent":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    match = {"accelerator_parent": "already holds the 'tpu' backend",
             "child_failed": "failed \\(exit 1\\)",
             "no_payload": "produced no DIST_CELLS_JSON"}[case]
    with pytest.raises(RuntimeError, match=match):
        run.bench_dist_elimination()
    assert len(spawned) == (0 if case == "accelerator_parent" else 1)
