"""Host time per request (ms) from a device program's call to its
return: inputs handed to the device and the program enqueued (the
program's ``streamsim.dispatch`` spans)."""

from chipbench import recorded

recorded.start()


def read(obs):
    return recorded.span_ms(obs, "streamsim.dispatch")
