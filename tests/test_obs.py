"""What the queue names for a profiler (core/obs.py): the passes' device
scopes in the compiled programs, the engines' dispatch spans and the
``gc`` spans, on the CPU."""

import gc
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import obs, pqueue
from repro.core.config import PQConfig
from repro.core.factory import EngineSpec, make_engine

BASE = PQConfig(a_max=64, r_max=64, seq_cap=512, n_buckets=16,
                bucket_cap=128, detach_min=4, detach_max=64, detach_init=8,
                chop_patience=8)
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _batch(t, w):
    return (np.zeros((t, w), np.float32), np.zeros((t, w), np.int32),
            np.zeros((t, w), bool), np.zeros((t,), np.int32))


def _scopes_in(hlo_text):
    """Every scope named as a component of an ``op_name``, bare or in a
    transform's name (``vmap(pq.head)``)."""
    return {part.rsplit("(", 1)[-1].rstrip(")")
            for name in _OP_NAME.findall(hlo_text)
            for part in name.split("/")} & set(obs.SCOPES)


@pytest.fixture(scope="module")
def pqe_text():
    eng = make_engine(EngineSpec(engine="pqe", width=64, base=BASE))
    return pqueue.tick_n.lower(eng.cfg, eng.init(), *_batch(2, 64)) \
        .compile().as_text()


def test_every_pass_scope_is_in_the_compiled_tick_n(pqe_text):
    assert _scopes_in(pqe_text) == set(obs.PQ_SCOPES)


def test_the_single_tick_carries_the_same_scopes():
    eng = make_engine(EngineSpec(engine="pqe", width=64, base=BASE))
    k, v, m, r = _batch(1, 64)
    text = pqueue.tick.lower(eng.cfg, eng.init(), k[0], v[0], m[0], r[0]) \
        .compile().as_text()
    assert _scopes_in(text) == set(obs.PQ_SCOPES)


def test_sharded_lanes_inherit_the_pass_scopes_and_add_their_own():
    from repro.core import sharded

    eng = make_engine(EngineSpec(engine="sharded", width=128, lanes=2,
                                 base=BASE))
    k, v, m, r = _batch(2, eng.width)
    text = sharded.tick_n.lower(eng.cfg, eng.init(seed=0), k, v, m, r) \
        .compile().as_text()
    assert _scopes_in(text) == (set(obs.PQ_SCOPES)
                                | {obs.SQ_ROUTE, obs.SQ_PREROUTE,
                                   obs.SQ_GRANTS})


def test_scope_names_are_module_constants_of_one_form():
    assert len(set(obs.SCOPES)) == len(obs.SCOPES) == 13
    for name in obs.SCOPES:
        assert re.fullmatch(r"(pq|sq|dq)\.[a-z_.]+", name), name
    assert obs.SPAN_TICK_N == "pq.tick_n" and obs.SPAN_GC == "gc"


# ---------------------------------------------------------------------------
# host spans, on a CPU profile
# ---------------------------------------------------------------------------

def _host_events(log_dir):
    from jax.profiler import ProfileData

    path = sorted(Path(log_dir).rglob("*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A profile of one engine tick_n and tick and two collections."""
    eng = make_engine(EngineSpec(engine="pqe", width=64, base=BASE))
    state = eng.init()
    batch = _batch(1, 64)
    state, _ = eng.tick_n(state, *batch)          # compiled outside
    state, _ = eng.tick(state, *(a[0] for a in batch))
    jax.block_until_ready(state)
    log_dir = tmp_path_factory.mktemp("profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with obs.gc_spans():
            state, res = eng.tick_n(state, *batch)
            jax.block_until_ready(res)
            state, res = eng.tick(state, *(a[0] for a in batch))
            jax.block_until_ready(res)
            gc.collect()
            gc.collect()
        gc.collect()                              # after the block: no span
    finally:
        jax.profiler.stop_trace()
    return _host_events(log_dir)


def test_engine_dispatch_opens_its_span_around_the_jitted_call(profiled):
    for span, program in ((obs.SPAN_TICK_N, "PjitFunction(tick_n)"),
                          (obs.SPAN_TICK, "PjitFunction(tick)")):
        spans = [(s, e) for n, s, e in profiled if n == span]
        calls = [(s, e) for n, s, e in profiled if n == program]
        assert len(spans) == 1 and calls, (span, program)
        (s0, e0), = spans
        assert any(s0 <= s and e <= e0 for s, e in calls)


def test_gc_spans_cover_each_collection_in_the_block(profiled):
    spans = [(s, e) for n, s, e in profiled if n == obs.SPAN_GC]
    assert len(spans) >= 2
    assert all(e >= s for s, e in spans)


def test_gc_spans_pair_start_and_stop_and_leave_no_callback(monkeypatch):
    log = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("open", self.name))

        def __exit__(self, *exc):
            log.append(("close", self.name))

    monkeypatch.setattr(obs, "span", Recorder)
    before = list(gc.callbacks)
    with obs.gc_spans():
        gc.collect()
        gc.collect()
        # a collection still open when the block ends is closed by it
        gc.callbacks[-1]("start", {})
    assert gc.callbacks == before
    assert log == [("open", "gc"), ("close", "gc")] * 3


def test_stats_have_no_dead_counter():
    fields = pqueue.PQStats._fields
    assert "local_elim" not in fields
    zeros = pqueue.PQStats.zeros()
    assert len(zeros) == len(fields) == 14
    # distinct buffers: tick donates the state
    assert len({id(z) for z in zeros}) == len(fields)
