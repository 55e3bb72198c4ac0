"""Share (%) of the member-steps the device programs compute that carry
no message: pow2 member and cell pads and pipeline drain steps,
100 x (1 - ``streamsim.member_steps`` / ``streamsim.padded_member_steps``)
over the window."""

from chipbench import recorded

recorded.start()


def read(obs):
    used = recorded.counter(obs, "streamsim.member_steps")
    padded = recorded.counter(obs, "streamsim.padded_member_steps")
    if used is None or not padded:
        return None
    return 100.0 * (1.0 - used / padded)
