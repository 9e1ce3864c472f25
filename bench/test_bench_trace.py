"""The trace reduction: busy union, idle gaps by host span, op and
collective time -- on hand-made traces and on a recorded one."""

from pathlib import Path

import numpy as np
import pytest

from bench import trace as tr

TESTDATA = Path(__file__).resolve().parent / "testdata"


def _t(ops, spans):
    return tr.Trace(ops=ops, spans=spans)


def test_busy_is_the_union_of_overlapping_ops():
    s = tr.reduce(_t({0: [("a", 10, 50), ("b", 20, 30), ("c", 40, 70),
                          ("d", 80, 90)]},
                     [("window", 0, 100)]))
    assert s.busy_s == pytest.approx(70e-9)
    assert s.window_s == pytest.approx(100e-9)
    assert s.idle_share == pytest.approx(0.3)


def test_ops_are_clipped_to_the_window():
    s = tr.reduce(_t({0: [("a", -50, 20), ("b", 90, 200)]},
                     [("window", 0, 100)]))
    assert s.busy_s == pytest.approx(30e-9)
    assert s.op_s == pytest.approx({"a": 20e-9, "b": 10e-9})


def test_op_time_is_self_time_under_nesting():
    ops = [("while.1", 0, 100), ("fusion.2", 10, 30), ("cond.3", 40, 90),
           ("fusion.4", 50, 60), ("fusion.5", 95, 100)]
    s = tr.reduce(_t({0: ops}, [("window", 0, 100)]))
    assert s.op_s == pytest.approx({"while.1": 25e-9, "fusion.2": 20e-9,
                                    "cond.3": 40e-9, "fusion.4": 10e-9,
                                    "fusion.5": 5e-9})
    assert s.busy_s == pytest.approx(100e-9)


def test_op_names_are_the_hlo_instruction_names():
    assert tr._op_name("%fusion.460 = s32[132096]{0} fusion(...)") == \
        "fusion.460"
    assert tr._op_name("all-gather.3") == "all-gather.3"


def test_busy_and_op_time_are_averaged_over_chips():
    s = tr.reduce(_t({0: [("f", 0, 60)], 1: [("f", 0, 20)]},
                     [("window", 0, 100)]))
    assert s.chips == 2
    assert s.busy_s == pytest.approx(40e-9)
    assert s.op_s["f"] == pytest.approx(40e-9)


def test_idle_gaps_go_to_the_host_span_that_overlaps_them_most():
    spans = [("window", 0, 100), ("generate", 0, 12), ("dispatch", 12, 25),
             ("pull", 55, 70), ("pull", 90, 95)]
    s = tr.reduce(_t({0: [("f", 20, 50), ("g", 72, 88)]}, spans))
    # gaps: [0,20) -> generate 12 vs dispatch 8; [50,72) -> pull 15;
    # [88,100) -> pull 5, the rest of it under no span
    assert s.idle_s == pytest.approx({"generate": 20e-9, "pull": 34e-9})
    s = tr.reduce(_t({0: [("f", 0, 50)]}, [("window", 0, 100)]))
    assert s.idle_s == pytest.approx({"other": 50e-9})


@pytest.mark.parametrize("name,coll", [
    ("all-reduce.3", True), ("all-gather-start.1", True),
    ("all-gather-done", True), ("collective-permute.7", True),
    ("reduce-scatter", True), ("all-to-all.2", True),
    ("fusion.12", False), ("reduce.4", False), ("copy-start", False),
])
def test_collectives_are_grouped_by_op_name(name, coll):
    s = tr.reduce(_t({0: [(name, 0, 10), ("fusion.1", 10, 30)]},
                     [("window", 0, 40)]))
    assert s.collective_s == pytest.approx(10e-9 if coll else 0.0)


def test_breakdown_lists_the_largest_first_and_at_most_ten():
    ops = {0: [(f"op{i}", 20 * i, 20 * i + i) for i in range(1, 15)]}
    s = tr.reduce(_t(ops, [("window", 0, 400)]))
    bd = s.breakdown()
    assert [n for n, _ in bd["device_ops"]] == [f"op{i}"
                                                for i in range(14, 4, -1)]
    assert len(bd["idle_gaps"]) <= 10
    assert all(isinstance(v, float) for _, v in bd["device_ops"])


def test_no_window_or_no_device_op_is_an_error():
    with pytest.raises(ValueError, match="window"):
        tr.reduce(_t({0: [("f", 0, 1)]}, []))
    with pytest.raises(ValueError, match="device op"):
        tr.reduce(_t({}, [("window", 0, 1)]))


# ---------------------------------------------------------------------------
# a recorded trace: the tiny four-chip cell, 50 ms traced on a v5e host
# ---------------------------------------------------------------------------

RECORDED = TESTDATA / "tiny_dist.xplane.pb.xz"
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "collective-permute",
                       "reduce-scatter", "all-to-all")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import lzma

    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(lzma.decompress(RECORDED.read_bytes()))
    return tr.load(path)


def _merge(intervals):
    """Plain interval merge, one at a time."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def test_recorded_trace_has_four_chips_and_the_host_spans(recorded):
    assert sorted(recorded.ops) == [0, 1, 2, 3]
    names = {n for n, _, _ in recorded.spans}
    assert {"window", "generate", "dispatch", "pull"} <= names
    for ops in recorded.ops.values():
        assert ops and all(" = " not in n and not n.startswith("%")
                           for n, _, _ in ops)


def test_recorded_busy_matches_a_plain_interval_merge(recorded):
    s = tr.reduce(recorded)
    (w0, w1), = [(a, b) for n, a, b in recorded.spans if n == "window"]
    busy = []
    for ops in recorded.ops.values():
        clipped = [(max(a, w0), min(b, w1)) for _, a, b in ops
                   if min(b, w1) > max(a, w0)]
        busy.append(sum(e - s for s, e in _merge(clipped)) * 1e-9)
    assert s.chips == 4
    assert s.busy_s == pytest.approx(np.mean(busy), rel=1e-9)
    assert 0 < s.busy_s < s.window_s == pytest.approx((w1 - w0) * 1e-9)
    # the idle gaps, put down to host spans, are the rest of the window
    assert sum(s.idle_s.values()) == pytest.approx(s.window_s - s.busy_s,
                                                   rel=1e-6)
    assert set(s.idle_s) <= set(tr.HOST_SPANS) | {"other"}
    assert s.idle_s.get("dispatch", 0) + s.idle_s.get("pull", 0) > 0


def test_recorded_collectives_are_found_by_name(recorded):
    s = tr.reduce(recorded)
    coll = [n for n in s.op_s if n.startswith(COLLECTIVE_PREFIXES)]
    assert coll, "the four-chip tick holds collectives"
    assert s.collective_s == pytest.approx(sum(s.op_s[n] for n in coll))
    assert 0 < s.collective_s < s.busy_s
