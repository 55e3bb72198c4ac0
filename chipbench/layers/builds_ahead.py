"""Static builds per request that start while a program of the same
``run_many`` call is enqueued and not yet waited on: the program's
``streamsim.builds_ahead`` counter."""

from chipbench import recorded

recorded.start()


def read(obs):
    return recorded.counter(obs, "streamsim.builds_ahead")
