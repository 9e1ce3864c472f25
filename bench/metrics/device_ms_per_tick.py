"""device_ms_per_tick: device busy milliseconds (union of op intervals,
averaged over the chips) per tick of the traced window."""


def read(obs):
    if obs.trace is None or not obs.ticks:
        return None
    return 1e3 * obs.trace.busy_s / obs.ticks
