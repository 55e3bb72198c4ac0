"""Host time of engine construction per request (ms): the program's
``streamsim.engine`` spans in ``run_many``, the feasibility probe of
each stack group included."""

from chipbench import recorded

recorded.start()


def read(obs):
    return recorded.span_ms(obs, "streamsim.engine")
