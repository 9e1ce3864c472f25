"""tick_p95_ms: the 95th percentile, over every tick of the window, of
the milliseconds from a batch's dispatch to its results on the host."""

import numpy as np


def read(obs):
    if not obs.ticks:
        return None
    return float(np.percentile(obs.tick_s, 95)) * 1e3
