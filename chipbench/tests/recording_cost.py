"""Cost of recording the program's spans, measured on a TPU.

    python3 chipbench/tests/recording_cost.py <cell> <seed> <requests>

Sends ``<requests>`` requests of a benchmark cell back to back in one
process, alternating recording off and on (``repro.core.spans``), and
prints the median wall time of each half and the median of the
differences of adjacent (off, on) pairs.  Alternating keeps drifts of
the machine out of the difference.
"""

from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> None:
    import numpy as np

    from chipbench import generator, run, trace
    from repro.core import spans
    name, seed, n = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    cell = run.load_cell(ROOT, name)
    run.device_check(cell.chips)
    run.compile_cache(ROOT)
    traffic = generator.Traffic(cell.mix, cell.cfg["architectures"], seed)
    client = run.Client(cell.cfg)
    for k in range(2):
        client.send(client.specs(traffic.request(k, generator.WARMUP)))
    walls: dict = {False: [], True: []}
    for k in range(2 * (n // 2)):
        on = bool(k % 2)
        specs = client.specs(traffic.request(k))
        t0 = time.perf_counter()
        if on:
            with spans.recording(), spans.span(trace.REQUEST):
                client.send(specs)
        else:
            client.send(specs)
        walls[on].append(time.perf_counter() - t0)
    off, on_ = np.array(walls[False]), np.array(walls[True])
    base = float(np.median(off))
    pair = float(np.median(on_ - off))
    print(f"{name}: {len(off)} pairs; median off {base!r} s, on "
          f"{float(np.median(on_))!r} s; median of (on - off) {pair!r} s "
          f"= {100 * pair / base!r}% of the median request")


if __name__ == "__main__":
    main()
