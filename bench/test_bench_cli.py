"""The command refuses to run without a TPU, and ``BENCHMARK.json``
keeps the benchmark's contract."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "exact_w1024.hold",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    return not any(line.startswith("{") for line in p.stdout.splitlines())


def test_cli_exits_nonzero_without_a_tpu():
    p = _cli(ROOT, {"BENCH_RUN": "anything"})
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert _no_result(p)


def test_cli_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert _no_result(p)


def test_benchmark_json_keys_and_command():
    assert list(BENCH) == ["command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


CONFIG_FILES = sorted((ROOT / "bench" / "configs").glob("*.json"))


@pytest.mark.parametrize("path", CONFIG_FILES, ids=lambda p: p.stem)
def test_config_entry_matches_its_file(path):
    """Every configuration file states its deployment; one that
    ``BENCHMARK.json`` lists matches its entry and has a cell."""
    f = json.loads(path.read_text())
    assert f["name"] == path.stem and NAME.match(f["name"])
    assert {"source", "spec", "chips", "resident", "engine_seed",
            "guarantee", "reduced", "assumed"} <= set(f)
    assert all(k in f for k in f["reduced"])
    harness.load_config(f["name"])         # its guarantee is one it knows
    for cfg in BENCH["configs"]:
        if cfg["name"] != f["name"]:
            continue
        assert set(cfg) == {"name", "source", "file", "reduced", "why"}
        assert cfg["file"] == f"bench/configs/{cfg['name']}.json"
        assert f["source"] == cfg["source"] and f["reduced"] == cfg["reduced"]
        assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])
        for text in (cfg["source"], cfg["why"]):
            assert 1 <= len(text) <= 200
            assert "\n" not in text and "\t" not in text


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4)
    assert len(cell["why"]) <= 200
    cfg = harness.load_config(cell["config"])
    assert cfg["chips"] == cell["chips"]
    harness.load_mix(cell["traffic"])
    reported = {m["name"] for m in harness.metrics_of(BENCH, cell["name"],
                                                      traced=False)}
    assert "setup_s" in reported and len(reported) >= 2
    assert harness.metrics_of(BENCH, cell["name"], traced=True)


def test_metric_entries():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    cells = {c["name"] for c in BENCH["workloads"]}
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
        assert callable(harness.load_reader(m["name"]))
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert next(m for m in BENCH["end_to_end"]
                if m["name"] == "setup_s")["bound"] <= 0.25


@pytest.mark.parametrize("name", ["relaxed_L8", "relaxed_D4"])
def test_stated_envelope_is_the_programs_relax_bound(name):
    """The configuration states its rank-error limit; it is the c of the
    engine's own contract at this width."""
    from repro.core import sharded
    from repro.core.factory import _dist_cfg_of, make_engine

    cfg = harness.load_config(name)
    spec = harness.engine_spec(cfg)
    r = spec.width // 2
    if spec.engine == "dist":
        c = sharded.relax_bound(_dist_cfg_of(spec).shard, r)
    else:
        c = make_engine(spec).relax_bound(r)
    assert cfg["guarantee"]["rank_err_max"] == c - r
