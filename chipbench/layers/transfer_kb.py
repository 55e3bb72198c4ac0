"""KiB per request handed to the device programs and copied back: the
program's ``streamsim.h2d_bytes`` plus ``streamsim.d2h_bytes``."""

from chipbench import recorded

recorded.start()


def read(obs):
    h2d = recorded.counter(obs, "streamsim.h2d_bytes")
    d2h = recorded.counter(obs, "streamsim.d2h_bytes")
    if h2d is None or d2h is None:
        return None
    return (h2d + d2h) / 1024.0
