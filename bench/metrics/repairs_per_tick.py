"""repairs_per_tick: moveHead, chopHead, rebalance and spill events
(summed over lanes) per tick of the window."""

NAMES = ("n_movehead", "n_chophead", "n_rebalance", "n_spill")


def read(obs):
    c = obs.counters
    if c is None or not obs.ticks or "n_movehead" not in c:
        return None
    return sum(c[n] for n in NAMES) / obs.ticks
