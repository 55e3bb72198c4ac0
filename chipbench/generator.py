"""The one traffic generator: a closed loop of simulation requests.

A traffic mix is a JSON file ``chipbench/traffic/<name>.json`` whose
``lanes`` is the number of seeds per architecture in each request: each
request runs every architecture of the configuration, and the program
stacks the seeds of one architecture as lanes of one device program.

One client sends request ``k + 1`` when request ``k`` has returned, the
way a sweep script calls ``run_many``.  Request ``k``'s seeds come from
``--seed``, a stream id and ``k``: the window and the warm-up use
different streams, so no request repeats another and every request of
one mix has the same shape.
"""

from __future__ import annotations

import json
import os

import numpy as np

WINDOW, WARMUP = 0, 1


def load(root: str, name: str) -> dict:
    with open(os.path.join(root, "chipbench", "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if not 1 <= int(mix.get("lanes", 0)) <= 64:
        raise ValueError(f"traffic {name}: lanes must be 1 to 64")
    return mix


class Traffic:
    """Requests of one mix over one configuration's architectures."""

    def __init__(self, mix: dict, archs: list, seed: int) -> None:
        self.archs = list(archs)
        self.lanes = int(mix["lanes"])
        self.seed = int(seed) & (2 ** 64 - 1)

    def request(self, k: int, stream: int = WINDOW) -> list:
        """``[(arch, [seed, ...]), ...]`` of request ``k``."""
        raw = np.random.SeedSequence([self.seed, stream, k]).generate_state(
            len(self.archs) * self.lanes, np.uint64)
        seeds = [int(x) >> 1 for x in raw]
        return [(a, seeds[i * self.lanes:(i + 1) * self.lanes])
                for i, a in enumerate(self.archs)]
