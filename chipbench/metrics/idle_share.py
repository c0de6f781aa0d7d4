"""Device: share of the traced window in which no operation ran on the
chip."""


def read(obs):
    t = obs.trace
    if t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
