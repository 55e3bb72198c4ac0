"""Readings that the limits of ``correct`` are set from.

Run from the root of a checkout, on a machine with a TPU::

    python3 chipbench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed it sends as many requests as a run of the cell checks
(the first ``SAMPLE`` of the window) and
compares them with the plain reference, as a run does: these are the
program's readings, whose largest is a limit's lower reading.  Then the
control takes the program's place: the same reference computed in
float32, one precision below the configuration's float64.  Its smallest
reading is a limit's upper reading.  One JSON line per seed, and last a
line with the largest program reading and the smallest control reading
of each number.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from chipbench import check, generator  # noqa: E402
from chipbench import run as bench  # noqa: E402


def checked_requests(traffic: generator.Traffic) -> list:
    """The first ``SAMPLE`` window requests."""
    return [traffic.request(k) for k in range(bench.SAMPLE)]


def control_item(cfg: dict, ref, request: list) -> tuple:
    """The float32 reference in the program's place: results and
    summary throughputs shaped like the program's."""
    results, thr = [], []
    for arch, seeds in request:
        out = ref.simulate(cfg, arch, seeds, np.float32)
        for lane in range(len(seeds)):
            c = out["consume"][:, lane].astype(np.float64)
            results.append(types.SimpleNamespace(
                consume_times=c,
                publish_starts=np.sort(out["publish"][:, lane]).astype(
                    np.float64)))
            thr.append(ref.throughput(c))
    return request, results, thr


def readings(argv: list, root: str = ROOT) -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = bench.load_cell(root, args.workload)
    bench.device_check(cell.chips)
    bench.compile_cache(root)
    sys.path.insert(0, os.path.join(root, "src"))
    cfg = cell.cfg
    ref = check.load_reference(root, cfg["pattern"])
    client = bench.Client(cfg)
    rows = []
    for seed in args.seeds:
        traffic = generator.Traffic(cell.mix, cfg["architectures"], seed)
        program, control = [], []
        for request in checked_requests(traffic):
            results, summaries = client.send(client.specs(request))
            program.append((request, results,
                            [s.throughput_msgs_s for s in summaries]))
            control.append(control_item(cfg, ref, request))
        # one float64 reference per (arch, seeds), which the program and
        # the control are both compared with
        kept: dict = {}

        def simulate(cfg_: dict, arch: str, seeds: list) -> dict:
            key = (arch, tuple(seeds))
            if key not in kept:
                kept[key] = ref.simulate(cfg_, arch, seeds)
            return kept[key]
        once = types.SimpleNamespace(simulate=simulate,
                                     throughput=ref.throughput)
        rows.append({"seed": seed,
                     "program": check.sample_gaps(cfg, once, program),
                     "control": check.sample_gaps(cfg, once, control)})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main() -> None:
    rows = readings(sys.argv[1:])
    names = rows[0]["program"]
    print(json.dumps({
        "lower": {k: max(r["program"][k] for r in rows) for k in names},
        "upper": {k: min(r["control"][k] for r in rows) for k in names},
        "seeds": len(rows)}))


if __name__ == "__main__":
    main()
