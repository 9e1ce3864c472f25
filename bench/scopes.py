"""Device time by the program's named scopes, and idle gaps by host event.

This reads beside :mod:`bench.trace`, which it leaves as it is:
:func:`load` takes the :class:`bench.trace.Trace` that
``bench.trace.load`` reads from an ``.xplane.pb`` and adds

* the scope of every device op: the innermost ``pq.*``, ``sq.*`` or
  ``dq.*`` component of the op's ``op_name`` metadata in the module that
  ran it (``other`` where it has none).  A fusion carries the
  ``op_name`` of its root instruction, which is XLA's own rule, so the
  whole fusion goes to its root's scope.  The modules come from the
  profile's ``/host:metadata`` plane, which holds each executed module's
  HLO when the profile was taken with
  ``ProfileOptions.enable_hlo_proto``; the op names there are the ones
  the trace's ``XLA Ops`` line shows;
* the host events of the dispatching side, read from two host lines:
  the line that holds the harness's ``window`` span (the Python thread
  that dispatches: the harness's spans, the program's ``pq.tick_n`` /
  ``pq.tick`` and ``gc`` spans, and the runtime's Python-side events
  such as ``PjitFunction(tick_n)`` and ``DevicePutWithSharding``), and
  each ``main/<tid>`` line, where the runtime's C++ client records what
  the dispatching call waits on (``Wait for donation holds``,
  ``AllocateRawBuffer``).  The runtime's worker threads
  (``py_xla_execute``, ``pjrt-tpu-tasks``, ...) are not read: they run
  beside the dispatching thread and say nothing of what it waits on.

:func:`reduce` gives a :class:`ScopedSummary`: the base
:class:`bench.trace.TraceSummary`, computed by ``bench.trace.reduce``
unchanged; device seconds per chip by scope (op self time, so the
scopes add up to the ops' time); the window's idle time put down,
instant by instant, to the innermost host event open on the dispatching
lines at that instant (the shorter where both lines hold one; ``other``
where none is open; the ``window`` span itself is no candidate), so a
gap between two ticks splits into the end of one dispatch, the pull,
the next batch's making and the next launch; the idle time inside the
program's dispatch spans; and the idle time while a program runs (the
device between the ops of one program, from the ``XLA Modules`` line).
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import trace as trace_mod

#: scope of a device op that carries none of the program's scopes
OTHER = "other"
#: the program's dispatch spans around a call into a tick program
LAUNCH_SPANS = ("pq.tick_n", "pq.tick")
#: scope prefix of the rare repairs
REPAIR_PREFIX = "pq.repair."
#: per-tick metrics by their scopes
SCOPE_METRICS = {
    "head_ms_per_tick": ("pq.head",),
    "combine_ms_per_tick": ("pq.combine",),
    "scatter_ms_per_tick": ("pq.scatter",),
    "repair_ms_per_tick": (REPAIR_PREFIX,),
}

# a scope component, bare or inside a transform's name: vmap(pq.head)
_SCOPE = re.compile(r"^(?:[A-Za-z_]+\()*((?:pq|sq|dq)\.[A-Za-z0-9_.]+)\)*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(calls|to_apply|body|condition|true_computation|false_computation|"
    r"branch_computations)=(\{[^}]*\}|%?[\w.\-]+)")
_METADATA_PLANE = "/host:metadata"
_MODULE_LINE = "XLA Modules"
_HOST_MAIN = "main/"


def scope_of(op_name: str) -> str:
    """The innermost program scope in an ``op_name`` path, or ``other``;
    a transform keeps the scope inside its own name (``vmap(pq.head)``)."""
    for part in reversed(op_name.split("/")):
        m = _SCOPE.match(part)
        if m:
            return m.group(1)
    return OTHER


# ---------------------------------------------------------------------------
# HLO text
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Instr:
    name: str
    opcode: str
    op_name: str
    computation: str
    called: Tuple[Tuple[str, str], ...]  # (attribute, computation) it calls


def _opcode(rest: str) -> str:
    """The opcode of an instruction's text after ``name = ``: the word
    before the first ``(`` that follows the result type (a tuple type
    is skipped whole; its layouts may hold parentheses)."""
    i = 0
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        i += 1
    else:
        i = rest.find(" ")
    rest = rest[i:].lstrip()
    return rest.split("(", 1)[0].strip()


def parse_hlo(text: str) -> Dict[str, Instr]:
    """Every instruction of an HLO module's text, by name.  A fusion
    whose own metadata names no ``op_name`` takes its root's, as XLA
    gives a fusion its root's metadata."""
    out: Dict[str, Instr] = {}
    roots: Dict[str, str] = {}          # computation -> its root's op_name
    comp = ""
    for line in text.splitlines():
        if not line or line[0] not in " \t":
            if line.rstrip().endswith("{"):
                head = line.split("(", 1)[0].split()
                comp = head[-1].lstrip("%") if head else ""
            continue
        body = line.strip()
        if " = " not in body:
            continue
        lhs, rest = body.split(" = ", 1)
        name = lhs.split()[-1].lstrip("%")
        m = _OP_NAME.search(rest)
        op_name = m.group(1) if m else ""
        if lhs.startswith("ROOT"):
            roots[comp] = op_name
        called = tuple((attr, c.strip().lstrip("%"))
                       for attr, comps in _CALLED.findall(rest)
                       for c in comps.strip("{}").split(",") if c.strip())
        out[name] = Instr(name, _opcode(rest), op_name, comp, called)
    for name, i in out.items():
        fused = dict(i.called).get("calls")
        if i.opcode == "fusion" and not i.op_name and fused in roots:
            out[name] = dataclasses.replace(i, op_name=roots[fused])
    return out


def op_scopes(text: str) -> Dict[str, str]:
    """Scope of every instruction of an HLO module's text."""
    return {n: scope_of(i.op_name) for n, i in parse_hlo(text).items()}


# ---------------------------------------------------------------------------
# reading the profile
# ---------------------------------------------------------------------------

def _varint(b: bytes, i: int):
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes):
    """(field number, value) of one protobuf message, in wire order;
    a length-delimited value is its bytes, a varint its integer."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, v


def hlo_modules(path) -> Dict[str, str]:
    """The HLO text of each module in the profile's metadata plane, by
    the module's name as the ``XLA Modules`` line shows it
    (``jit_tick_n(<program id>)``).  Empty where the profile was taken
    without ``enable_hlo_proto``.

    Field numbers: ``XSpace.planes`` 1; ``XPlane.name`` 2,
    ``.event_metadata`` 4 (map entries: key 1, value 2),
    ``.stat_metadata`` 5; ``XEventMetadata.name`` 2, ``.stats`` 5;
    ``XStat.metadata_id`` 1, ``.bytes_value`` 6; ``HloProto.hlo_module``
    1 (tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto)."""
    from jax._src.lib import _jax

    out: Dict[str, str] = {}
    for num, plane in _fields(memoryview(Path(path).read_bytes())):
        if num != 1:
            continue
        fields = list(_fields(plane))
        if bytes(dict(fields).get(2, b"")) != _METADATA_PLANE.encode():
            continue
        stat_names = {}
        for f, entry in fields:
            if f == 5:
                meta = dict(_fields(dict(_fields(entry))[2]))
                stat_names[meta.get(1)] = bytes(meta.get(2, b"")).decode()
        for f, entry in fields:
            if f != 4:
                continue
            meta = list(_fields(dict(_fields(entry))[2]))
            name = bytes(dict(meta).get(2, b"")).decode()
            for g, stat in meta:
                st = dict(_fields(stat)) if g == 5 else {}
                if stat_names.get(st.get(1)) == "Hlo Proto" and 6 in st:
                    module = bytes(dict(_fields(st[6]))[1])
                    out[name] = _jax.HloModule.from_serialized_hlo_module_proto(
                        module).to_string()
    return out


@dataclasses.dataclass
class Scoped:
    """A trace as ``bench.trace.load`` reads it, with the scope of each
    device op and the dispatching side's host events."""

    trace: trace_mod.Trace
    op_scope: Dict[int, List[str]]          # chip -> scope of each op
    host: List[List[Tuple[str, int, int]]]  # per host line: (name, start, end)
    scoped: bool                            # the profile held the modules
    # chip -> (start, end) of each program run (the ``XLA Modules`` line)
    programs: Dict[int, List[Tuple[int, int]]] = dataclasses.field(
        default_factory=dict)


def load(path) -> Scoped:
    """Read one ``.xplane.pb``: ``bench.trace.load``'s reading, the scope
    of each op from the module that ran it, and the host lines."""
    from jax.profiler import ProfileData

    base = trace_mod.load(path)
    scopes = {name: op_scopes(text)
              for name, text in hlo_modules(path).items()}
    data = ProfileData.from_file(str(path))
    op_scope: Dict[int, List[str]] = {}
    programs: Dict[int, list] = {}
    host_lines: list = []
    for plane in data.planes:
        m = trace_mod._DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            lines = {line.name: line for line in plane.lines}
            mods = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                           e.name) for e in lines[_MODULE_LINE].events) \
                if _MODULE_LINE in lines else []
            starts = np.array([s for s, _, _ in mods], np.int64)
            found = []
            if trace_mod._OP_LINE in lines:
                for e in lines[trace_mod._OP_LINE].events:
                    k = int(np.searchsorted(starts, int(e.start_ns),
                                            side="right")) - 1
                    table = scopes.get(mods[k][2], {}) if k >= 0 else {}
                    found.append(table.get(trace_mod._op_name(e.name), OTHER))
            op_scope[chip] = found
            programs[chip] = [(a, b) for a, b, _ in mods]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(e.name, int(e.start_ns),
                           int(e.start_ns + e.duration_ns))
                          for e in line.events]
                if line.name.startswith(_HOST_MAIN) or any(
                        n == trace_mod.WINDOW_SPAN for n, _, _ in events):
                    host_lines.append(events)
    for chip, ops in base.ops.items():
        if len(op_scope.get(chip, ())) != len(ops):
            raise ValueError(f"chip {chip}: the scopes do not line up with "
                             f"the ops bench.trace.load read")
    return Scoped(trace=base, op_scope=op_scope, host=host_lines,
                  scoped=any(s != OTHER for t in scopes.values()
                             for s in t.values()),
                  programs=programs)


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ScopedSummary:
    """What :func:`reduce` reads from a :class:`Scoped` trace."""

    base: trace_mod.TraceSummary
    scope_s: Dict[str, float]           # per chip, averaged; empty: unscoped
    idle_by_host: Dict[str, float]      # per chip, averaged
    launch_idle_s: Optional[float]      # None: the trace holds no launch span
    launch_s: float = 0.0               # host seconds inside launch spans
    program_idle_s: float = 0.0         # idle while a program runs, per chip
    op_scopes: Dict[str, str] = dataclasses.field(default_factory=dict)

    def breakdown(self, top: int = 10) -> dict:
        out = self.base.breakdown(top)
        out["scopes"] = [[k, v] for k, v in
                         sorted(self.scope_s.items(), key=lambda kv: -kv[1])]
        idle = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])
        if len(idle) > top:
            idle = idle[:top - 1] + [["rest", sum(v for _, v in idle[top - 1:])]]
        out["idle_by_host"] = [[k, v] for k, v in idle]
        return out

    def per_tick_ms(self, ticks: int) -> Dict[str, Optional[float]]:
        """The five per-tick metrics; ``None`` where the trace cannot
        give them (no scoped module, no launch span, no tick)."""
        out: Dict[str, Optional[float]] = {}
        for name, prefixes in SCOPE_METRICS.items():
            out[name] = None
            if ticks and self.scope_s:
                out[name] = 1e3 * sum(v for k, v in self.scope_s.items()
                                      if k.startswith(prefixes)) / ticks
        out["launch_idle_ms_per_tick"] = (
            None if self.launch_idle_s is None or not ticks
            else 1e3 * self.launch_idle_s / ticks)
        return out


def _innermost(events) -> List[Tuple[int, int, int]]:
    """One host line's timeline: disjoint ``(start, end, i)`` pieces,
    each covered by ``events[i]`` as the innermost event open there.
    Events on one line nest; a sweep keeps the open ones on a stack."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1],
                                                      -events[i][2]))
    out: list = []
    stack: list = []
    t = 0

    def close_until(x):
        nonlocal t
        while stack and events[stack[-1]][2] <= x:
            i = stack.pop()
            if events[i][2] > t:
                out.append((t, events[i][2], i))
                t = events[i][2]

    for i in order:
        s = events[i][1]
        close_until(s)
        if stack and s > t:
            out.append((t, s, stack[-1]))
        t = max(t, s)
        stack.append(i)
    close_until(np.iinfo(np.int64).max)
    return out


def _host_timeline(lines) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
    """Disjoint sorted intervals per event name: the instants at which
    that name is the innermost host event open on the dispatching
    lines, the shorter event where both lines hold one."""
    pieces = []
    for events in lines:
        pieces += [(s, e, events[i][2] - events[i][1], events[i][0])
                   for s, e, i in _innermost(events)]
    bounds = sorted({t for s, e, _, _ in pieces for t in (s, e)})
    # at each elementary interval, the shortest event among the pieces
    # that cover it (at most one piece per line)
    pieces.sort()
    active: list = []
    j = 0
    by_name: Dict[str, list] = {}
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        while j < len(pieces) and pieces[j][0] <= lo:
            active.append(pieces[j])
            j += 1
        active = [p for p in active if p[1] > lo]
        if active:
            name = min(active, key=lambda p: p[2])[3]
            runs = by_name.setdefault(name, [])
            if runs and runs[-1][1] == lo:
                runs[-1][1] = hi
            else:
                runs.append([lo, hi])
    return {n: (np.array([a for a, _ in r], np.int64),
                np.array([b for _, b in r], np.int64))
            for n, r in by_name.items()}


def reduce(sc: Scoped) -> ScopedSummary:
    """The base summary, device time by scope, idle time by host event
    and idle time inside the launch spans, all inside the window."""
    base = trace_mod.reduce(sc.trace)
    trace = sc.trace
    win = [(s, e) for n, s, e in trace.spans if n == trace_mod.WINDOW_SPAN]
    w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    chips = sorted(c for c, evs in trace.ops.items() if evs)

    host = [[ev for ev in line if ev[0] != trace_mod.WINDOW_SPAN]
            for line in sc.host]
    timeline = _host_timeline(host)
    launch = [(s, e) for line in host for n, s, e in line
              if n in LAUNCH_SPANS]
    lu = trace_mod._union(np.array([s for s, _ in launch], np.int64),
                          np.array([e for _, e in launch], np.int64))

    scope_s: Dict[str, float] = {}
    op_scopes: Dict[str, str] = {}
    idle: Dict[str, float] = {}
    launch_idle = program_idle = 0.0
    for c in chips:
        st = np.array([s for _, s, _ in trace.ops[c]], np.int64)
        en = np.array([e for _, _, e in trace.ops[c]], np.int64)
        st, en = np.clip(st, w0, w1), np.clip(en, w0, w1)
        keep = en > st
        dur = trace_mod._self_time(st[keep], en[keep])
        if sc.scoped:
            kept = np.asarray(sc.op_scope[c], object)[keep]
            for s, d in zip(kept, dur):
                scope_s[s] = scope_s.get(s, 0.0) + d * 1e-9
            names = np.asarray([n for n, _, _ in trace.ops[c]], object)[keep]
            op_scopes.update(zip(names, kept))
        us, ue = trace_mod._union(st[keep], en[keep])
        gs = np.concatenate([[w0], ue])
        ge = np.concatenate([us, [w1]])
        g = ge > gs
        gs, ge = gs[g], ge[g]
        if not gs.size:
            continue
        launch_idle += float((trace_mod._covered(*lu, ge)
                              - trace_mod._covered(*lu, gs)).sum()) * 1e-9
        runs = sc.programs.get(c, [])
        pu = trace_mod._union(np.array([a for a, _ in runs], np.int64),
                              np.array([b for _, b in runs], np.int64))
        program_idle += float((trace_mod._covered(*pu, ge)
                               - trace_mod._covered(*pu, gs)).sum()) * 1e-9
        rest = int((ge - gs).sum())
        for name, (hs, he) in timeline.items():
            t = int((trace_mod._covered(hs, he, ge)
                     - trace_mod._covered(hs, he, gs)).sum())
            if t:
                idle[name] = idle.get(name, 0.0) + t * 1e-9
                rest -= t
        if rest:
            idle[OTHER] = idle.get(OTHER, 0.0) + rest * 1e-9
    k = len(chips)
    ls, le = np.clip(lu[0], w0, w1), np.clip(lu[1], w0, w1)
    return ScopedSummary(
        base=base, scope_s={n: v / k for n, v in scope_s.items()},
        idle_by_host={n: v / k for n, v in idle.items()},
        launch_idle_s=launch_idle / k if launch else None,
        launch_s=float((le - ls).sum()) * 1e-9,
        program_idle_s=program_idle / k, op_scopes=op_scopes)
