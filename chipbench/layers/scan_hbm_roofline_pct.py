"""The device program's share of its memory roofline (%): the least
bytes the profiled requests must move (``chipbench.work.least_bytes``)
at the chip's peak HBM bandwidth, over the device busy time.  Bound by
bytes: the scan does no matrix work."""


def read(obs):
    r = obs.reduced
    if r is None or r.busy_s <= 0 or obs.traced_bytes <= 0:
        return None
    return 100.0 * obs.traced_bytes / obs.peak["hbm_bytes_per_s"] / r.busy_s
