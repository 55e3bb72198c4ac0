"""Share of the profiled window (%) in which no operation ran on the
chip."""


def read(obs):
    r = obs.reduced
    if r is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
