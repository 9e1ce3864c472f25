"""Whole runs of tiny cells on the CPU: the result line, the metrics,
and cells, mixes and metrics found by name."""

import json
import shutil

import jax
import pytest

import numpy as np

from bench import generate, harness, reference, tiny
from bench import trace as trace_mod

KEYS = ["correct", "attempted", "failed", "metrics", "device"]
CHECKS = ["bad_pairs", "count_gap", "rank_err_max", "resident_gap",
          "size_gap", "dropped"]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    d = tmp_path_factory.mktemp("bench")
    return d, tiny.bench_tree(d)


def _run(tree, cell, traced=False, seed=2**31 + 5, **kw):
    d, bench = tree
    lines = []
    r = harness.run_cell(bench, cell, seed=seed, seconds=0.3, traced=traced,
                         devices=jax.devices(), bench_dir=d,
                         emit=lines.append, **kw)
    return r, lines


@pytest.mark.parametrize("cell", ["tiny_exact.hold", "tiny_exact.uniform",
                                  "tiny_relaxed.uniform"])
def test_sound_run_is_correct_with_end_to_end_metrics(tree, cell):
    r, lines = _run(tree, cell)
    assert list(r) == KEYS + ["checks"]
    assert r["correct"] is True and r["failed"] == 0
    assert list(r["checks"]) == CHECKS
    assert r["attempted"] > 600
    m = r["metrics"]
    assert set(m) == {"ops_per_s", "tick_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert m["ops_per_s"]["unit"] == "ops/s"
    if cell.startswith("tiny_exact"):
        assert r["checks"]["rank_err_max"]["value"] == 0
    assert r["device"]["platform"] == "cpu"
    assert r["device"]["count"] == len(jax.devices())
    text = "\n".join(lines)
    assert "compiles_in_window=0 " in text
    assert "generator_share=" in text
    json.dumps(r)                        # the result line is plain JSON


@pytest.mark.xfail(strict=True, reason=(
    "program fault: on the hold mix the sharded queue leaves keys unserved, "
    "so its rank error grows past relax_bound(r) - r (about 600 against "
    "320 at W=128, L=8, on every seed tried)"))
def test_relaxed_hold_stays_within_its_envelope(tree):
    r, _ = _run(tree, "tiny_relaxed.hold")
    assert r["checks"]["bad_pairs"]["value"] == 0
    assert r["checks"]["resident_gap"]["value"] == 0
    assert r["correct"] is True, r["checks"]


def test_hold_loop_is_closed(tree):
    """Each hold tick's adds land above the largest key served before
    it, and nowhere near a clock fixed in advance."""
    d, _ = tree
    cfg = harness.load_config("tiny_exact", d)
    eng = harness.build_engine(cfg, jax.devices())
    traffic = generate.Traffic(harness.load_mix("hold", d), width=eng.width,
                               resident=cfg["resident"], seed=2**33 + 1)
    log = harness._Log()
    drv = harness._Loop(eng, eng.init(seed=0), traffic,
                        generate.IdPool(reference.ID_BOUND), log)
    drv.load(traffic.load_keys())
    n_load = len(log)
    for _ in range(150):
        drv.mix()
    clock = traffic.clock0
    gaps = []
    for t in range(n_load, len(log)):
        if t > n_load and log.served_keys[t - 1].size:
            clock = max(clock, float(log.served_keys[t - 1].max()))
        adds = log.add_keys[t].astype(np.float64)
        assert adds.min() >= clock - 1e-2 * max(1.0, abs(clock))
        gaps.append(adds.mean() - clock)
    # the clock of the last tick sent, well past where it started
    assert drv.clock == clock > traffic.clock0 + 100 * 1e5 / 600
    # mean increment of 8 key spacings above the clock
    step = 1e5 / cfg["resident"]
    assert abs(np.mean(gaps) / (8 * step) - 1) < 0.2


def test_same_seed_same_served_stream(tree):
    a, _ = _run(tree, "tiny_relaxed.uniform", seed=11)
    b, _ = _run(tree, "tiny_relaxed.uniform", seed=11)
    assert a["checks"] == b["checks"]


def _fake_trace(log_dir):
    """A trace with two chips' ops and host spans on one clock."""
    return trace_mod.Trace(
        ops={0: [("fusion.1", 100, 400), ("all-gather.2", 400, 500)],
             1: [("fusion.1", 100, 300)]},
        spans=[("window", 0, 1000), ("dispatch", 0, 150),
               ("pull", 500, 900)])


def test_traced_run_gives_per_layer_metrics_and_breakdown(tree, monkeypatch):
    monkeypatch.setattr(trace_mod, "load", _fake_trace)
    monkeypatch.setattr(trace_mod, "find_xplane", lambda d: d)
    r, _ = _run(tree, "tiny_exact.hold", traced=True)
    assert list(r) == KEYS + ["breakdown", "checks"]
    assert r["correct"] is True
    assert set(r["metrics"]) == {
        "device_idle_share", "device_ms_per_tick", "combine_add_share",
        "elim_share", "repairs_per_tick", "collective_ms_per_tick",
        "rank_err_p99"}
    assert r["metrics"]["rank_err_p99"]["value"] == 0
    assert r["device"]["busy_s"] == pytest.approx(300e-9)
    assert r["device"]["window_s"] == pytest.approx(1000e-9)
    assert r["metrics"]["device_idle_share"]["value"] == pytest.approx(70.0)
    bd = r["breakdown"]
    assert set(bd) == {"device_ops", "idle_gaps"}
    assert bd["device_ops"][0][0] == "fusion.1"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_traced_run_without_device_ops_leaves_trace_metrics_out(tree):
    # the CPU backend writes no device plane
    r, lines = _run(tree, "tiny_relaxed.uniform", traced=True)
    assert "breakdown" not in r and "busy_s" not in r["device"]
    assert set(r["metrics"]) == {"combine_add_share", "elim_share",
                                 "repairs_per_tick", "rank_err_p99"}
    assert any(s.startswith("# trace:") for s in lines)


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    bench = tiny.bench_tree(tmp_path)
    cfg = dict(tiny.CONFIGS["tiny_exact"])
    cfg["resident"] = 256
    (tmp_path / "configs" / "tiny_new.json").write_text(json.dumps(cfg))
    mix = json.loads((tmp_path / "traffic" / "uniform.json").read_text())
    mix["load"]["hi"] = mix["add"]["hi"] = 10.0
    (tmp_path / "traffic" / "narrow.json").write_text(json.dumps(mix))
    (tmp_path / "metrics" / "served_per_tick.py").write_text(
        "def read(obs):\n    return obs.served / obs.ticks\n")
    bench["workloads"].append({"name": "tiny_new.narrow", "config": "tiny_new",
                               "traffic": "narrow", "chips": 1})
    bench["end_to_end"].append({"name": "served_per_tick", "unit": "keys",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["tiny_new.narrow"]})
    r = harness.run_cell(bench, "tiny_new.narrow", seed=3, seconds=0.2,
                         traced=False, devices=jax.devices(),
                         bench_dir=tmp_path, emit=lambda s: None)
    assert r["correct"] is True
    # 64-wide ticks at p_add 0.5: 32 removes, all served at this depth
    assert r["metrics"]["served_per_tick"]["value"] == 32
    assert "served_per_tick" not in harness.metrics_of(
        bench, "tiny_exact.hold", traced=False)


def test_a_reader_that_finds_nothing_leaves_its_metric_out(tree, tmp_path):
    d, bench = tree
    shutil.copytree(d, tmp_path / "b")
    (tmp_path / "b" / "metrics" / "setup_s.py").write_text(
        "def read(obs):\n    return None\n")
    r = harness.run_cell(bench, "tiny_exact.uniform", seed=1, seconds=0.1,
                         traced=False, devices=jax.devices(),
                         bench_dir=tmp_path / "b", emit=lambda s: None)
    assert "setup_s" not in r["metrics"] and "ops_per_s" in r["metrics"]


def test_metrics_of_follows_workload_lists():
    bench = harness.load_benchmark()
    for cell in ("exact_w1024.hold", "exact_w1024.uniform"):
        e2e = [m["name"] for m in harness.metrics_of(bench, cell,
                                                      traced=False)]
        assert e2e == ["ops_per_s", "tick_p95_ms", "setup_s"]
        layer = [m["name"] for m in harness.metrics_of(bench, cell,
                                                        traced=True)]
        assert layer == ["device_idle_share", "device_ms_per_tick",
                         "combine_add_share", "elim_share",
                         "repairs_per_tick"]
    # a metric that lists other cells is left out; one with no list is in
    bench["end_to_end"][1]["workloads"] = ["other.cell"]
    bench["end_to_end"][0].pop("workloads", None)
    e2e = [m["name"] for m in harness.metrics_of(bench, "exact_w1024.hold",
                                                  traced=False)]
    assert e2e == ["ops_per_s", "setup_s"]


def test_counters_are_read_as_window_deltas(tree):
    d, bench = tree
    cfg = harness.load_config("tiny_relaxed", d)
    eng = harness.build_engine(cfg, jax.devices())
    c = harness.read_counters(eng, eng.init(seed=0))
    assert {"add_seq", "add_imm_elim", "n_preroute_elim", "n_movehead",
            "n_router_dropped", "n_dropped"} <= set(c)
    assert all(c[k] == 0 for k in ("add_seq", "n_movehead", "n_dropped"))
