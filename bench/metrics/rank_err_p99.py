"""rank_err_p99: the 99th percentile, over every key served in the
window, of its rank error against the exact union (the exact queue's
serve position subtracted)."""

import numpy as np


def read(obs):
    if not obs.rank_err.size:
        return None
    return float(np.percentile(obs.rank_err, 99))
