"""Share (%) of the device programs' scan steps that only drain replies
left in the pending-reply buffer: 100 x ``streamsim.drain_steps`` /
``streamsim.scan_steps`` over the window (feedback programs)."""

from chipbench import recorded

recorded.start()


def read(obs):
    drain = recorded.counter(obs, "streamsim.drain_steps")
    steps = recorded.counter(obs, "streamsim.scan_steps")
    if drain is None or not steps:
        return None
    return 100.0 * drain / steps
