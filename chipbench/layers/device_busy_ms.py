"""Device busy time per request (ms): the union of the intervals in which
an operation ran on the chip, over the profiled requests."""


def read(obs):
    r = obs.reduced
    if r is None or r.busy_s <= 0:
        return None
    return 1e3 * r.busy_s / r.n_requests
