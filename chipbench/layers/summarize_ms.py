"""Host time of ``metrics.summarize`` per request (ms): the program's
``streamsim.summarize`` spans."""

from chipbench import recorded

recorded.start()


def read(obs):
    return recorded.span_ms(obs, "streamsim.summarize")
