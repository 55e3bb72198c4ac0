"""Engines ``run_many`` constructs per request: the program's
``streamsim.engines`` counter, probes included."""

from chipbench import recorded

recorded.start()


def read(obs):
    return recorded.counter(obs, "streamsim.engines")
