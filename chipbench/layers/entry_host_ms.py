"""Host time of the entry layer per request (ms): the request's wall time
less the timed layer spans under it (static build, jitter draws, device
program, assembly): ``run_many``, engine construction and cost model,
spec grouping and ``summarize``."""

import math


def read(obs):
    rows = [w - sum(s.values()) for w, s in zip(obs.walls, obs.spans)]
    if not rows or any(math.isnan(r) for r in rows):
        return None
    return 1e3 * sum(rows) / len(rows)
