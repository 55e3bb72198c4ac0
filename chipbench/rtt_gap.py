"""Per-message round-trip times of a feedback cell against its plain
reference.

Run from the root of a checkout, on a machine with a TPU::

    python3 chipbench/rtt_gap.py --workload <cell> --seeds <n> [<n> ...]

``correct`` compares consume times, publish starts and throughput, not
round-trip times, so a fault in a reply's last hop or in the RTT
arithmetic would not show there.  For each seed this sends the requests
a run of the cell checks (``control.checked_requests``) and reads
``rtt_rel_gap``: the widest gap of a result's per-message RTT to the
reference's (``chipbench/refs/<pattern>.py``), as a share of the
result's span, the reference's last consume time in its lane, as
``check.sample_gaps`` takes it.  Then the same for the reference in
float32 in the program's place.  One JSON line per seed, and last the
largest program reading and the smallest control reading.  The
benchmark's own runs never run this.
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

from chipbench import check, control, generator  # noqa: E402
from chipbench import run as bench  # noqa: E402


def rtt_rel_gap(results: list, want: dict) -> float:
    """Widest RTT gap over one architecture's lanes, as a share of each
    lane's span; a result without every reply reads ``inf``."""
    gap = 0.0
    for lane, r in enumerate(results):
        span = float(want["consume"][:, lane].max())
        gap = max(gap, check._rel_gap(np.asarray(r.rtts),
                                      want["rtt"][:, lane], span))
    return gap


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
        program = ctl = 0.0
        for request in control.checked_requests(traffic):
            results = client.send(client.specs(request))[0]
            i = 0
            for arch, seeds in request:
                want = ref.simulate(cfg, arch, seeds)
                program = max(program, rtt_rel_gap(
                    results[i:i + len(seeds)], want))
                f32 = ref.simulate(cfg, arch, seeds, np.float32)
                ctl = max(ctl, rtt_rel_gap(
                    [types.SimpleNamespace(rtts=f32["rtt"][:, k].astype(
                        np.float64)) for k in range(len(seeds))], want))
                i += len(seeds)
        rows.append({"seed": seed, "program": {"rtt_rel_gap": program},
                     "control": {"rtt_rel_gap": ctl}})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main() -> None:
    rows = readings(sys.argv[1:])
    print(json.dumps({
        "lower": {"rtt_rel_gap": max(r["program"]["rtt_rel_gap"]
                                     for r in rows)},
        "upper": {"rtt_rel_gap": min(r["control"]["rtt_rel_gap"]
                                     for r in rows)},
        "seeds": len(rows)}))


if __name__ == "__main__":
    main()
