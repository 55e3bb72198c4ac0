"""Programs per request enqueued while an earlier program of the same
``run_many`` call had not yet been waited on: the program's
``streamsim.programs_ahead`` counter."""

from chipbench import recorded

recorded.start()


def read(obs):
    return recorded.counter(obs, "streamsim.programs_ahead")
