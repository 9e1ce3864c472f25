"""elim_share: adds eliminated against a remove -- immediately, after
aging in the batch, or before routing -- per live add of the window, in
percent."""

NAMES = ("add_imm_elim", "add_upc_elim", "n_preroute_elim")


def read(obs):
    c = obs.counters
    if c is None or not obs.live_adds or "add_imm_elim" not in c:
        return None
    return 100.0 * sum(c.get(n, 0.0) for n in NAMES) / obs.live_adds
