"""collective_ms_per_tick: device milliseconds of all-reduce,
all-gather, collective-permute, reduce-scatter and all-to-all ops
(averaged over the chips) per tick of the traced window; nothing where
the trace holds no collective."""


def read(obs):
    if obs.trace is None or not obs.ticks or not obs.trace.collective_s:
        return None
    return 1e3 * obs.trace.collective_s / obs.ticks
