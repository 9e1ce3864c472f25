"""device_idle_share: 1 - (union of device-op intervals / traced
window), averaged over the chips, in percent."""


def read(obs):
    if obs.trace is None:
        return None
    return 100.0 * obs.trace.idle_share
