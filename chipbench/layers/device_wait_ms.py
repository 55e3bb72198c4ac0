"""Host time per request (ms) spent waiting for the device programs to
finish after their dispatch (the program's ``streamsim.wait`` spans)."""

from chipbench import recorded

recorded.start()


def read(obs):
    return recorded.span_ms(obs, "streamsim.wait")
