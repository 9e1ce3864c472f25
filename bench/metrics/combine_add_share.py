"""combine_add_share: adds combined into the sequential head
(``add_seq``, summed over lanes) per live add of the window, in
percent."""


def read(obs):
    c = obs.counters
    if c is None or not obs.live_adds or "add_seq" not in c:
        return None
    return 100.0 * c["add_seq"] / obs.live_adds
