"""Reduce a profiler trace to device busy time, idle gaps and op time.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
a :class:`Trace`: the device operations of each chip (the ``XLA Ops``
line of each ``/device:TPU:<n>`` plane) and the benchmark's own host
spans (``jax.profiler.TraceAnnotation`` names in :data:`HOST_SPANS`).
:func:`reduce` turns that into a :class:`TraceSummary`:

* busy time -- the union of the op intervals of each chip inside the
  ``window`` span, averaged over the chips;
* idle gaps -- the rest of the window, each gap put down to the host
  span that overlaps it most (``other`` where none does);
* op self time by name (a ``while`` or ``conditional`` op holds the ops
  of its body on the same line; their time is its children's, not its
  own), and the time of collective ops (names that start with
  ``all-reduce``, ``all-gather``, ``collective-permute``,
  ``reduce-scatter`` or ``all-to-all``, their ``-start``/``-done``
  halves included), each averaged over the chips.

An op is named by its HLO instruction name (``fusion.460``), the part
of the event's name before `` = ``.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

#: host spans written by the harness around each step of a tick
HOST_SPANS = ("generate", "dispatch", "pull", "check")
#: the host span that bounds the measured window
WINDOW_SPAN = "window"

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_LINE = "XLA Ops"
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|collective-permute|reduce-scatter|all-to-all)")


@dataclasses.dataclass
class Trace:
    """Device ops per chip and host spans, times in ns on one clock."""

    ops: Dict[int, List[Tuple[str, int, int]]]        # chip -> (name, start, end)
    spans: List[Tuple[str, int, int]]                  # (name, start, end)


@dataclasses.dataclass
class TraceSummary:
    chips: int
    window_s: float
    busy_s: float                   # per chip, averaged
    op_s: Dict[str, float]          # per chip, averaged
    collective_s: float             # per chip, averaged
    idle_s: Dict[str, float]        # per chip, averaged, by host span

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def find_xplane(log_dir) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path) -> Trace:
    """Read one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: Dict[int, list] = {}
    spans: list = []
    wanted = set(HOST_SPANS) | {WINDOW_SPAN}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            chip = ops.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == _OP_LINE:
                    chip.extend((_op_name(e.name), int(e.start_ns),
                                 int(e.start_ns + e.duration_ns))
                                for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, int(e.start_ns),
                              int(e.start_ns + e.duration_ns))
                             for e in line.events if e.name in wanted)
    return Trace(ops=ops, spans=spans)


def _op_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].lstrip("%")


def _self_time(st: np.ndarray, en: np.ndarray) -> np.ndarray:
    """Each interval's length less the parts its children cover; an
    interval that starts inside another is that one's child."""
    self_t = (en - st).astype(np.int64)
    stack: list = []
    for i in np.lexsort((-en, st)):
        while stack and en[stack[-1]] <= st[i]:
            stack.pop()
        if stack:
            p = stack[-1]
            self_t[p] -= min(en[i], en[p]) - st[i]
        stack.append(i)
    return self_t


def _union(starts: np.ndarray, ends: np.ndarray):
    """Disjoint sorted intervals covering the given ones."""
    if not starts.size:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > run_end[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return s[first], run_end[last]


def _covered(s: np.ndarray, e: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Length of disjoint sorted intervals ``[s, e)`` before each ``t``."""
    if not s.size:
        return np.zeros(t.shape)
    cum = np.concatenate([[0], np.cumsum(e - s)])
    i = np.searchsorted(s, t, side="right") - 1
    before = cum[np.maximum(i, 0)]
    part = np.clip(t - s[np.maximum(i, 0)], 0, (e - s)[np.maximum(i, 0)])
    return np.where(i >= 0, before + part, 0)


def reduce(trace: Trace) -> TraceSummary:
    """Busy time, idle gaps by host span and op time inside the window.

    Raises ``ValueError`` where the trace holds no window span or no
    device op inside it."""
    win = [(s, e) for n, s, e in trace.spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no window span")
    w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    chips = sorted(c for c, evs in trace.ops.items() if evs)
    if not chips:
        raise ValueError("the trace holds no device op")

    labelled = {}
    for name in HOST_SPANS:
        ss = np.array([s for n, s, _ in trace.spans if n == name], np.int64)
        ee = np.array([e for n, _, e in trace.spans if n == name], np.int64)
        labelled[name] = _union(ss, ee)

    busy = 0.0
    op_s: Dict[str, float] = {}
    coll = 0.0
    idle: Dict[str, float] = {}
    for c in chips:
        names = [n for n, _, _ in trace.ops[c]]
        st = np.array([s for _, s, _ in trace.ops[c]], np.int64)
        en = np.array([e for _, _, e in trace.ops[c]], np.int64)
        st, en = np.clip(st, w0, w1), np.clip(en, w0, w1)
        keep = en > st
        dur = _self_time(st[keep], en[keep])
        for n, d in zip(np.asarray(names, object)[keep], dur):
            op_s[n] = op_s.get(n, 0.0) + d * 1e-9
            if _COLLECTIVE.match(n):
                coll += d * 1e-9
        us, ue = _union(st[keep], en[keep])
        busy += float((ue - us).sum()) * 1e-9
        # idle gaps: the window minus the busy union
        gs = np.concatenate([[w0], ue])
        ge = np.concatenate([us, [w1]])
        g = ge > gs
        gs, ge = gs[g], ge[g]
        if not gs.size:
            continue
        over = np.stack([_covered(*labelled[n], ge) - _covered(*labelled[n], gs)
                         for n in HOST_SPANS])
        best = over.argmax(axis=0)
        lab = np.where(over.max(axis=0) > 0, best, -1)
        for i, name in enumerate(HOST_SPANS):
            t = float((ge - gs)[lab == i].sum()) * 1e-9
            if t:
                idle[name] = idle.get(name, 0.0) + t
        t = float((ge - gs)[lab == -1].sum()) * 1e-9
        if t:
            idle["other"] = idle.get("other", 0.0) + t
    k = len(chips)
    return TraceSummary(
        chips=k, window_s=(w1 - w0) * 1e-9, busy_s=busy / k,
        op_s={n: v / k for n, v in op_s.items()}, collective_s=coll / k,
        idle_s={n: v / k for n, v in idle.items()})
