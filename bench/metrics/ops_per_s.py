"""ops_per_s: accepted live adds plus served removes, over the whole
window's seconds on the host clock."""


def read(obs):
    if not obs.ticks:
        return None
    dropped = 0.0
    if obs.counters is not None:
        dropped = (obs.counters.get("n_dropped", 0.0)
                   + obs.counters.get("n_router_dropped", 0.0))
    return (obs.live_adds - dropped + obs.served) / obs.window_s
