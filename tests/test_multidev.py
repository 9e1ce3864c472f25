"""Multi-device integration (8 fake devices in a subprocess — device count
locks at first jax init, so these cannot share the main pytest process).

The subprocess (tests/multidev_checks.py) exits 42 when the host device
count could not be forced (e.g. a platform that ignores
--xla_force_host_platform_device_count); that becomes a clean skip here
instead of an opaque assertion.  On failure the FULL stderr tail is part
of the assertion message, so import errors and tracebacks inside the
subprocess surface in the pytest report instead of being swallowed.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

_SCRIPT = Path(__file__).parent / "multidev_checks.py"
_ROOT = Path(__file__).parent.parent
_SKIP_EXIT = 42


def _run(which: str, timeout: int = 900):
    env = {**os.environ,
           "PYTHONPATH": str(_ROOT / "src"),
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    proc = subprocess.run([sys.executable, str(_SCRIPT), which],
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=str(_ROOT))
    if proc.returncode == _SKIP_EXIT:
        reason = (proc.stderr.strip().splitlines() or ["no reason given"])[-1]
        pytest.skip(f"multidev harness: {reason}")
    assert proc.returncode == 0, (
        f"{which} failed (exit {proc.returncode})\n"
        f"--- stdout ---\n{proc.stdout[-2000:]}\n"
        f"--- stderr ---\n{proc.stderr[-8000:]}")
    return proc.stdout


@pytest.mark.slow
def test_dist_sharded_8dev():
    out = _run("dist")
    assert "OK dist_sharded" in out


@pytest.mark.slow
def test_dist_sharded_equals_single_device():
    out = _run("dist_equiv")
    assert "OK dist_equiv" in out


@pytest.mark.slow
def test_dist_resize_8dev():
    out = _run("dist_resize")
    assert "OK dist_resize" in out


@pytest.mark.slow
def test_moe_expert_parallel_parity():
    out = _run("moe")
    assert "OK moe_parity" in out


@pytest.mark.slow
def test_sharded_train_step_executes():
    out = _run("train")
    assert "OK sharded_train_step" in out


@pytest.mark.slow
def test_sharded_decode_executes():
    out = _run("decode")
    assert "OK sharded_decode" in out


def test_chip_smoke_dist_phase_4dev():
    out = _run("chip_smoke_dist")
    assert "OK chip_smoke_dist" in out


def test_dist_tick_n_carries_every_scope_4dev():
    out = _run("scopes_dist")
    assert "OK scopes_dist" in out
