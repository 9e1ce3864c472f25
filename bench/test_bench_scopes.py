"""Device time by the program's scopes and idle gaps by host event
(bench/scopes.py): the HLO reading, the scan body's coverage by scopes
on the CPU, hand-made traces and recorded ones."""

import dataclasses
import gc
import lzma
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import scopes as sc
from bench import trace as tr

TESTDATA = Path(__file__).resolve().parent / "testdata"


# ---------------------------------------------------------------------------
# scopes from op_name metadata and HLO text
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op_name,scope", [
    ("jit(tick_n)/while/body/closed_call/pq.combine/jit(clip)/min",
     "pq.combine"),
    ("jit(tick_n)/pq.preds/cond/branch_1_fun/pq.repair.move/gather",
     "pq.repair.move"),
    ("jit(tick_n)/cond/branch_1_fun/vmap(pq.head)/sort", "pq.head"),
    ("jit(dist_tick_n)/shard_map/dq.gather/all_gather", "dq.gather"),
    ("jit(tick_n)/sq.route/jit(_argsort)/sort", "sq.route"),
    ("jit(tick_n)/while/body/dynamic_update_slice", sc.OTHER),
    ("", sc.OTHER),
    ("jit(tick_n)/pq/add", sc.OTHER),
])
def test_the_innermost_scope_of_an_op_name(op_name, scope):
    assert sc.scope_of(op_name) == scope


HLO = """\
HloModule jit_tick_n

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %negate.1 = f32[8]{0} negate(%param_0), metadata={op_name="jit(f)/pq.head/neg"}
}

%wrapped_computation (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %reduce-window.2 = f32[8]{0} reduce-window(%param_0.1), window={size=8}
}

%branch_a (p: f32[8]) -> f32[8] {
  ROOT %p = f32[8]{0} parameter(0)
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> (f32[8], u32[]) {
  %Arg_0.1 = f32[8]{0} parameter(0)
  %fusion.460 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/pq.combine/select_n"}
  %fusion.7 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation
  %wrapped = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%wrapped_computation
  %copy-start.1 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start(%Arg_0.1)
  %cond.3 = f32[8]{0} conditional(%c, %Arg_0.1, %Arg_0.1), branch_computations={%branch_a, %branch_a}, metadata={op_name="jit(f)/pq.preds/cond"}
  ROOT %tuple = (f32[8]{0}, u32[]) tuple(%fusion.460, %c)
}
"""


def test_parse_hlo_reads_names_opcodes_metadata_and_calls():
    ins = sc.parse_hlo(HLO)
    f = ins["fusion.460"]
    assert (f.opcode, f.op_name, f.computation) == (
        "fusion", "jit(f)/pq.combine/select_n", "main.9")
    assert f.called == (("calls", "fused_computation"),)
    assert ins["copy-start.1"].opcode == "copy-start"   # a tuple type
    assert ins["cond.3"].called == (("branch_computations", "branch_a"),
                                    ("branch_computations", "branch_a"))
    assert ins["negate.1"].computation == "fused_computation"


def test_a_fusion_without_metadata_takes_its_roots_scope():
    scopes = sc.op_scopes(HLO)
    # its own metadata first: pq.combine, not the root's pq.head
    assert scopes["fusion.460"] == "pq.combine"
    assert scopes["fusion.7"] == "pq.head"
    assert scopes["wrapped"] == sc.OTHER
    assert scopes["tuple"] == sc.OTHER


# ---------------------------------------------------------------------------
# the compiled tick program: every device op of the scan body is scoped
# ---------------------------------------------------------------------------

#: scan plumbing that belongs to no pass: the stacking of each tick's
#: results and the loop counter
PLUMBING = ("jit(tick_n)/while/body/dynamic_update_slice",
            "jit(tick_n)/while/body/dynamic_slice",
            "jit(tick_n)/while/body/add")
#: ops that JAX lowers through a cached private function, which keeps no
#: name stack: the cumulative sums (``reduce-window``) of the repairs'
#: flatten and extraction, which the CPU backend's wrapping fusion
#: leaves with no metadata at all
NO_NAME_STACK = ("reduce-window",)
DEVICE_OPS = ("sort", "gather", "scatter", "fusion")
BRANCHES = ("body", "condition", "branch_computations", "true_computation",
            "false_computation")


def _scan_body_ops(text):
    ins = sc.parse_hlo(text)
    scan, = [i for i in ins.values()
             if i.opcode == "while" and i.op_name == "jit(tick_n)/while"]
    by_comp = {}
    for i in ins.values():
        by_comp.setdefault(i.computation, []).append(i)
    todo, seen = [dict(scan.called)["body"]], set()
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo += [n for i in by_comp.get(c, ())
                     if i.opcode in ("conditional", "while")
                     for a, n in i.called if a in BRANCHES]
    return ins, [i for c in seen for i in by_comp.get(c, ())
                 if i.opcode in DEVICE_OPS]


def _root_opcode(ins, fusion):
    fused = dict(fusion.called).get("calls")
    roots = [i for i in ins.values() if i.computation == fused]
    return roots[-1].opcode if roots else None


def test_every_device_op_of_the_scan_body_carries_a_pass_scope():
    from repro.core import pqueue
    from repro.core.config import PQConfig

    cfg = PQConfig(a_max=64, r_max=64, seq_cap=512, n_buckets=16,
                   bucket_cap=128, detach_min=4, detach_max=64,
                   detach_init=8, chop_patience=8)
    t, w = 2, 64
    text = pqueue.tick_n.lower(
        cfg, pqueue.init(cfg), np.zeros((t, w), np.float32),
        np.zeros((t, w), np.int32), np.zeros((t, w), bool),
        np.zeros((t,), np.int32)).compile().as_text()
    ins, ops = _scan_body_ops(text)
    assert len(ops) > 100
    bad = []
    for i in ops:
        if sc.scope_of(i.op_name).startswith("pq."):
            continue
        if i.op_name in PLUMBING:
            continue
        if not i.op_name and _root_opcode(ins, i) in NO_NAME_STACK:
            continue
        bad.append((i.name, i.opcode, i.op_name))
    assert not bad
    scoped = {sc.scope_of(i.op_name) for i in ops}
    assert {"pq.head", "pq.combine", "pq.scatter", "pq.finish",
            "pq.repair.move", "pq.repair.chop"} <= scoped


# ---------------------------------------------------------------------------
# the HLO in a profile's metadata plane (a CPU profile)
# ---------------------------------------------------------------------------

def test_the_profile_holds_each_executed_modules_hlo(tmp_path):
    @jax.jit
    def f(x):
        with jax.named_scope("pq.head"):
            return jax.numpy.sort(x) * 2

    x = np.arange(16, dtype=np.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = True
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    modules = sc.hlo_modules(tr.find_xplane(tmp_path))
    name, = [n for n in modules if n.startswith("jit_f(")]
    assert "pq.head" in set(sc.op_scopes(modules[name]).values())


def test_a_profile_without_the_modules_gives_none(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False       # as bench/harness.py takes it
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert sc.hlo_modules(tr.find_xplane(tmp_path)) == {}


# ---------------------------------------------------------------------------
# the reduction, on hand-made traces
# ---------------------------------------------------------------------------

def _scoped(ops, op_scope, spans, host):
    return sc.Scoped(trace=tr.Trace(ops=ops, spans=spans), op_scope=op_scope,
                     host=host, scoped=True)


NESTED = [("while.1", 0, 100), ("fusion.2", 10, 30), ("cond.3", 40, 90),
          ("fusion.4", 50, 60), ("fusion.5", 95, 100)]
NESTED_SCOPES = [sc.OTHER, "pq.head", "pq.preds", "pq.repair.move", sc.OTHER]


def test_scope_time_is_the_ops_self_time_and_unscoped_goes_to_other():
    s = sc.reduce(_scoped({0: NESTED}, {0: NESTED_SCOPES},
                          [("window", 0, 100)], []))
    assert s.scope_s == pytest.approx({
        sc.OTHER: 30e-9, "pq.head": 20e-9, "pq.preds": 40e-9,
        "pq.repair.move": 10e-9})
    assert sum(s.scope_s.values()) == pytest.approx(s.base.busy_s)
    assert s.scope_s["pq.head"] == pytest.approx(s.base.op_s["fusion.2"])


def test_scope_time_is_clipped_to_the_window_and_averaged_over_chips():
    ops = {0: [("f", -50, 20), ("g", 20, 60)], 1: [("f", 0, 40)]}
    s = sc.reduce(_scoped(ops, {0: ["pq.head", "pq.combine"],
                                1: ["pq.head"]},
                          [("window", 0, 100)], []))
    assert s.scope_s == pytest.approx({"pq.head": 30e-9,
                                       "pq.combine": 20e-9})


def test_the_base_summary_is_bench_trace_reduce_unchanged():
    spans = [("window", 0, 100), ("dispatch", 0, 40), ("pull", 60, 100)]
    trace = tr.Trace(ops={0: NESTED[1:2] + [("fusion.9", 45, 55)]},
                     spans=spans)
    s = sc.reduce(sc.Scoped(trace=trace, op_scope={0: ["pq.head"] * 2},
                            host=[spans], scoped=True))
    base = tr.reduce(trace)
    assert dataclasses.asdict(s.base) == dataclasses.asdict(base)
    bd = s.breakdown()
    assert bd["device_ops"] == base.breakdown()["device_ops"]
    assert bd["idle_gaps"] == base.breakdown()["idle_gaps"]


def test_idle_time_goes_to_the_innermost_host_event_open_at_each_instant():
    ops = {0: [("f", 0, 20), ("g", 60, 80), ("h", 90, 100)]}
    python = [("window", 0, 100), ("dispatch", 15, 85),
              ("pq.tick_n", 18, 50), ("PjitFunction(tick_n)", 19, 45),
              ("DevicePutWithSharding", 19, 22)]
    main = [("Wait for donation holds", 22, 44)]
    s = sc.reduce(_scoped(ops, {0: [sc.OTHER] * 3}, python[:1],
                          [python, main]))
    # gap [20, 60): the put 20-22, the wait 22-44 (shorter than the
    # PjitFunction open on the other line), PjitFunction 44-45,
    # pq.tick_n 45-50, dispatch 50-60; gap [80, 90): dispatch 80-85,
    # nothing but the window 85-90
    assert s.idle_by_host == pytest.approx({
        "DevicePutWithSharding": 2e-9, "Wait for donation holds": 22e-9,
        "PjitFunction(tick_n)": 1e-9, "pq.tick_n": 5e-9, "dispatch": 15e-9,
        sc.OTHER: 5e-9})
    assert sum(s.idle_by_host.values()) == pytest.approx(
        s.base.window_s - s.base.busy_s)
    # idle while the dispatching thread is inside pq.tick_n: [20, 50)
    assert s.launch_idle_s == pytest.approx(30e-9)


def test_a_gap_between_ticks_splits_over_the_phases_of_the_host():
    ops = {0: [("f", 0, 20), ("g", 60, 100)]}
    python = [("window", 0, 100), ("dispatch", 0, 25), ("pull", 25, 30),
              ("generate", 30, 40), ("dispatch", 40, 100),
              ("pq.tick_n", 40, 48), ("gc", 33, 36)]
    s = sc.reduce(_scoped(ops, {0: [sc.OTHER] * 2}, python[:1], [python]))
    assert s.idle_by_host == pytest.approx({
        "dispatch": 17e-9, "pull": 5e-9, "generate": 7e-9, "gc": 3e-9,
        "pq.tick_n": 8e-9})
    assert s.launch_idle_s == pytest.approx(8e-9)


def test_idle_inside_a_program_run_and_launch_time_are_counted():
    ops = {0: [("f", 10, 20), ("g", 30, 40), ("h", 60, 70)]}
    host = [[("window", 0, 100), ("pq.tick_n", 0, 12), ("pq.tick_n", 50, 55)]]
    scoped = sc.Scoped(trace=tr.Trace(ops=ops, spans=host[0][:1]),
                       op_scope={0: ["pq.head", "pq.combine", "pq.head"]},
                       host=host, scoped=True,
                       programs={0: [(5, 42), (58, 75)]})
    s = sc.reduce(scoped)
    # idle inside the runs: [5, 10), [20, 30), [40, 42), [58, 60), [70, 75)
    assert s.program_idle_s == pytest.approx(24e-9)
    assert s.launch_s == pytest.approx(17e-9)
    assert s.launch_idle_s == pytest.approx(15e-9)
    assert s.op_scopes == {"f": "pq.head", "g": "pq.combine", "h": "pq.head"}


def test_idle_by_host_is_averaged_over_chips():
    ops = {0: [("f", 0, 50)], 1: [("f", 0, 90)]}
    host = [[("window", 0, 100), ("pull", 40, 100)]]
    s = sc.reduce(_scoped(ops, {0: [sc.OTHER], 1: [sc.OTHER]},
                          host[0][:1], host))
    assert s.idle_by_host == pytest.approx({"pull": 30e-9})
    assert s.launch_idle_s is None


def test_breakdown_keeps_the_ten_largest_host_events_and_sums_the_rest():
    ops = {0: [(f"op{i}", 20 * i, 20 * i + 10) for i in range(14)]}
    host = [[("window", 0, 400)] + [(f"ev{i}", 20 * i + 10, 20 * i + 20)
                                    for i in range(14)]]
    s = sc.reduce(_scoped(ops, {0: ["pq.head"] * 14}, host[0][:1], host))
    idle = s.breakdown()["idle_by_host"]
    assert len(idle) == 10 and idle[-1][0] == "rest"
    assert sum(v for _, v in idle) == pytest.approx(
        s.base.window_s - s.base.busy_s)
    assert s.breakdown()["scopes"] == [["pq.head", pytest.approx(140e-9)]]


def test_per_tick_metrics_need_scopes_launch_spans_and_ticks():
    ops = {0: [("a", 0, 10), ("b", 10, 30), ("c", 30, 60), ("d", 60, 64),
               ("e", 64, 66), ("f", 70, 80)]}
    scopes = ["pq.head", "pq.combine", "pq.scatter", "pq.repair.move",
              "pq.repair.chop", "pq.finish"]
    host = [[("window", 0, 100), ("pq.tick_n", 60, 100)]]
    s = sc.reduce(_scoped(ops, {0: scopes}, host[0][:1], host))
    m = s.per_tick_ms(2)
    assert m == pytest.approx({
        "head_ms_per_tick": 5e-6, "combine_ms_per_tick": 10e-6,
        "scatter_ms_per_tick": 15e-6, "repair_ms_per_tick": 3e-6,
        "launch_idle_ms_per_tick": 12e-6})
    assert all(v is None for v in s.per_tick_ms(0).values())
    # no scoped module and no launch span: nothing to read
    bare = sc.reduce(sc.Scoped(trace=tr.Trace(ops=ops, spans=host[0][:1]),
                               op_scope={0: [sc.OTHER] * 6},
                               host=[host[0][:1]], scoped=False))
    assert bare.scope_s == {}
    assert all(v is None for v in bare.per_tick_ms(2).values())


# ---------------------------------------------------------------------------
# recorded traces
# ---------------------------------------------------------------------------

def _unpack(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(lzma.decompress((TESTDATA / name).read_bytes()))
    return path


@pytest.fixture(scope="module")
def dist_path(tmp_path_factory):
    return _unpack(tmp_path_factory, "tiny_dist.xplane.pb.xz")


def test_a_trace_without_modules_reads_as_before_with_host_events(dist_path):
    s = sc.reduce(sc.load(dist_path))
    base = tr.reduce(tr.load(dist_path))
    assert dataclasses.asdict(s.base) == dataclasses.asdict(base)
    assert s.scope_s == {} and s.launch_idle_s is None
    assert sum(s.idle_by_host.values()) == pytest.approx(
        base.window_s - base.busy_s, rel=1e-6)
    # the dispatch idle splits into the runtime's own events
    assert set(s.idle_by_host) - set(tr.HOST_SPANS) - {sc.OTHER}


def test_the_two_dispatching_host_lines_are_read(dist_path):
    scoped = sc.load(dist_path)
    names = [{n for n, _, _ in line} for line in scoped.host]
    assert len(names) == 2
    python, = [n for n in names if "window" in n]
    main, = [n for n in names if "window" not in n]
    assert {"dispatch", "PjitFunction(dist_tick_n)",
            "DevicePutWithSharding"} <= python
    assert {"Wait for donation holds", "AllocateRawBuffer"} <= main


def test_gc_spans_in_a_cpu_profile_are_paired(tmp_path):
    from repro.core import obs

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.gc_spans(), jax.profiler.TraceAnnotation("window"):
            gc.collect()
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(tr.find_xplane(tmp_path)))
    lines = [[(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
              for e in line.events]
             for p in data.planes if p.name.startswith("/host:")
             for line in p.lines]
    line, = [ln for ln in lines if any(n == "window" for n, _, _ in ln)]
    (w0, w1), = [(s, e) for n, s, e in line if n == "window"]
    gcs = [(s, e) for n, s, e in line if n == "gc"]
    assert len(gcs) >= 2
    assert all(w0 <= s <= e <= w1 for s, e in gcs)


# a tiny pqe cell (W=64, 600 keys), 9 ticks of ``tiny_exact.hold`` traced
# on a v5e by bench/scoped_run.py --tiny, the modules' HLO in the profile
SCOPED = "tiny_pqe_scoped.xplane.pb.xz"


@pytest.fixture(scope="module")
def scoped_path(tmp_path_factory):
    return _unpack(tmp_path_factory, SCOPED)


def test_recorded_scoped_trace_holds_the_executed_tick_module(scoped_path):
    modules = sc.hlo_modules(scoped_path)
    name, = modules
    assert name.startswith("jit_tick_n(")
    assert set(sc.op_scopes(modules[name]).values()) >= {
        "pq.head", "pq.combine", "pq.scatter", "pq.preds", "pq.finish"}


def test_recorded_busy_time_maps_to_the_pass_scopes(scoped_path):
    s = sc.reduce(sc.load(scoped_path))
    assert sum(s.scope_s.values()) == pytest.approx(s.base.busy_s)
    in_pq = sum(v for k, v in s.scope_s.items() if k.startswith("pq."))
    assert in_pq >= 0.95 * s.base.busy_s
    m = s.per_tick_ms(9)
    assert all(v is not None and v >= 0 for v in m.values())
    assert m["combine_ms_per_tick"] > 0 and m["launch_idle_ms_per_tick"] > 0


def test_recorded_existing_fields_read_as_without_the_new_path(scoped_path):
    s = sc.reduce(sc.load(scoped_path))
    base = tr.reduce(tr.load(scoped_path))
    assert dataclasses.asdict(s.base) == dataclasses.asdict(base)
    bd = s.breakdown()
    assert bd["device_ops"] == base.breakdown()["device_ops"]
    assert bd["idle_gaps"] == base.breakdown()["idle_gaps"]
    assert set(bd) == {"device_ops", "idle_gaps", "scopes", "idle_by_host"}


def test_recorded_idle_splits_the_dispatch_gap_by_host_event(scoped_path):
    s = sc.reduce(sc.load(scoped_path))
    idle = s.base.window_s - s.base.busy_s
    assert sum(s.idle_by_host.values()) == pytest.approx(idle, rel=1e-6)
    assert s.idle_by_host.get("pq.tick_n", 0) + s.idle_by_host.get(
        "PjitFunction(tick_n)", 0) > 0
    runtime = set(s.idle_by_host) - set(tr.HOST_SPANS) - {
        sc.OTHER, "pq.tick_n", "gc"}
    assert runtime
    assert 0 < s.launch_idle_s < idle
