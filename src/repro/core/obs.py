"""What the queue names for a profiler: device scopes and host spans.

Device scopes are ``jax.named_scope`` names, put on each pass where it
is defined (as a decorator), so that every caller inherits them.  Each
is HLO metadata only: every op a pass emits carries the pass's name in
its ``op_name`` (``.../pq.combine/...``, ``.../vmap(pq.head)/...``
under ``vmap``), so a profiler trace can put each device op down to the
pass that made it.  A scope adds no op and changes no fusion; an XLA
fusion takes the ``op_name`` of its root instruction.

=========================  ==========================================
scope                      code
=========================  ==========================================
``pq.head``                ``pqueue._tick_head``: sort, elimination, split
``pq.combine``             ``pqueue._pass_combine``: the head's merge
``pq.scatter``             ``pqueue._pass_scatter``: the parallel part's append
``pq.preds``               ``pqueue._tick_preds``, and the repairs' ``lax.cond``
``pq.repair.rebal_move``   ``pqueue._repair_rebal_move``
``pq.repair.rebalance``    ``pqueue._repair_rebalance``
``pq.repair.move``         ``pqueue._repair_move``: moveHead
``pq.repair.chop``         ``pqueue._repair_chop``: chopHead
``pq.finish``              ``pqueue._tick_finish``: counters, state assembly
``sq.route``               the sharded router (``sharded._route_*``)
``sq.preroute``            ``sharded._preroute_eliminate``
``sq.grants``              ``sharded._alloc_removes*``
``dq.gather``              the two lane-summary ``all_gather`` calls
=========================  ==========================================

Host spans are ``jax.profiler.TraceAnnotation`` names, on the
profiler's clock with the device ops: the engines open ``pq.tick`` and
``pq.tick_n`` around each call into a jitted tick program (argument
transfer, donation and launch, up to the call's return), and
:func:`gc_spans` opens ``gc`` around each garbage collection.  When no
profile is being taken a span costs one TraceMe check.
"""

from __future__ import annotations

import contextlib
import gc

import jax

PQ_HEAD = "pq.head"
PQ_COMBINE = "pq.combine"
PQ_SCATTER = "pq.scatter"
PQ_PREDS = "pq.preds"
PQ_REPAIR_REBAL_MOVE = "pq.repair.rebal_move"
PQ_REPAIR_REBALANCE = "pq.repair.rebalance"
PQ_REPAIR_MOVE = "pq.repair.move"
PQ_REPAIR_CHOP = "pq.repair.chop"
PQ_FINISH = "pq.finish"
SQ_ROUTE = "sq.route"
SQ_PREROUTE = "sq.preroute"
SQ_GRANTS = "sq.grants"
DQ_GATHER = "dq.gather"

#: the exact queue's passes, in the order a tick runs them
PQ_SCOPES = (PQ_HEAD, PQ_COMBINE, PQ_SCATTER, PQ_PREDS, PQ_REPAIR_REBAL_MOVE,
             PQ_REPAIR_REBALANCE, PQ_REPAIR_MOVE, PQ_REPAIR_CHOP, PQ_FINISH)
#: the relaxed queues' own stages, around the lanes' passes
LANE_SCOPES = (SQ_ROUTE, SQ_PREROUTE, SQ_GRANTS, DQ_GATHER)
SCOPES = PQ_SCOPES + LANE_SCOPES

SPAN_TICK = "pq.tick"
SPAN_TICK_N = "pq.tick_n"
SPAN_GC = "gc"


def span(name: str):
    """A host span on the profiler's clock."""
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def gc_spans():
    """Open a ``gc`` span around each garbage collection in the block:
    from the collector's ``"start"`` callback to its ``"stop"``."""
    open_span = []

    def on_gc(phase, info):
        del info
        if phase == "start":
            s = span(SPAN_GC)
            s.__enter__()
            open_span.append(s)
        elif open_span:
            open_span.pop().__exit__(None, None, None)

    gc.callbacks.append(on_gc)
    try:
        yield
    finally:
        gc.callbacks.remove(on_gc)
        while open_span:
            open_span.pop().__exit__(None, None, None)
