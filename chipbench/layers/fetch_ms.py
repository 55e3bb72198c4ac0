"""Host time per request (ms) copying the device programs' outputs to
the host (the program's ``streamsim.fetch`` spans)."""

from chipbench import recorded

recorded.start()


def read(obs):
    return recorded.span_ms(obs, "streamsim.fetch")
