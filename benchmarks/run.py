"""Benchmark harness: one module per paper table/figure + the roofline and
kernel micro-benches. Prints ``name,us_per_call,derived`` CSV.

Simulator cells run on the vectorized engine by default; ``--engine
heap`` is the escape hatch back to the exact reference engine (cache
keys carry the engine name, so the two never collide).

Simulator cells are disk-cached (results/bench_cache.json); delete the
cache to force re-measurement.  A cache file with legacy-format keys
(pre engine/params-aware keying) aborts the run loudly instead of
serving stale numbers.

Campaign mode executes a whole sweep grid as batched work (seed-stacked
engine runs + process fan-out; see ``src/repro/core/campaign.py``) and
writes the per-cell + averaged summaries to ``results/``::

    python -m benchmarks.run --campaign demo
    python -m benchmarks.run --campaign my_grid.json --workers 2 \\
        --campaign-out results/campaign_mygrid.json

The JSON spec mirrors ``CampaignSpec`` (axes, n_runs, params,
cell_params); ``demo`` runs a small built-in paper-style grid.
Campaign cells share the bench cache, so re-running a finished (or
interrupted) campaign is incremental."""

import argparse
import glob
import json
import math
import os
import sys
import time

from benchmarks import (
    bench_campaign, bench_chaos, bench_deployment_feasibility,
    bench_engine_scaling, bench_fig4_work_sharing, bench_fig5_rtt_cdf,
    bench_fig6_feedback_rtt, bench_fig7_broadcast_gather,
    bench_fig8_bg_cdf, bench_highspeed_projection, bench_jax_engine,
    bench_kernels, bench_overflow_regime, bench_payload_sweep,
    bench_roofline, bench_table1_workloads)
from benchmarks import common
from benchmarks.common import Cache, LegacyCacheError
from repro.compat import enable_compile_cache

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")

MODULES = [
    ("table1", bench_table1_workloads),
    ("fig4", bench_fig4_work_sharing),
    ("fig5", bench_fig5_rtt_cdf),
    ("fig6", bench_fig6_feedback_rtt),
    ("fig7", bench_fig7_broadcast_gather),
    ("fig8", bench_fig8_bg_cdf),
    ("highspeed", bench_highspeed_projection),
    ("payload_sweep", bench_payload_sweep),
    ("kernels", bench_kernels),
    ("roofline", bench_roofline),
    ("engine_scaling", bench_engine_scaling),
    ("overflow_regime", bench_overflow_regime),
    ("campaign", bench_campaign),
    ("deployment_feasibility", bench_deployment_feasibility),
    ("chaos", bench_chaos),
    ("jax_engine", bench_jax_engine),
]


def write_bench_json(name: str, rows: list, wall_s: float) -> str:
    """Machine-readable companion to the CSV: one
    ``results/BENCH_<name>.json`` per bench module (CI uploads them as
    artifacts), mapping each cell name to its measured row.

    NaN never reaches the artifact as a bare value: a non-finite
    ``us_per_call`` (infeasible cells) is written as ``null`` plus an
    explicit ``"status": "nan"`` marker, and the dump runs with
    ``allow_nan=False`` so any *other* NaN that sneaks into a row is a
    loud ``ValueError`` at write time — a stale artifact full of
    silent ``NaN`` literals (not even valid JSON) is how the chaos
    bench rot went unnoticed."""
    out = os.path.join(RESULTS_DIR, f"BENCH_{name}.json")
    os.makedirs(RESULTS_DIR, exist_ok=True)
    cells = {}
    for n, us, derived in rows:
        cell: dict = {"us_per_call": us, "derived": derived}
        if not math.isfinite(us):
            cell["us_per_call"] = None
            cell["status"] = "nan"
        cells[n] = cell
    payload = {
        "module": name,
        "wall_s": round(wall_s, 3),
        "engine_override": common.DEFAULT_ENGINE,
        "cells": cells,
    }
    with open(out, "w") as f:
        json.dump(payload, f, indent=1, allow_nan=False)
    return out


def _registered_artifact_names() -> set:
    """Every BENCH_<name>.json stem the current bench registry can
    produce: one per module plus one per *named* campaign."""
    names = {name for name, _ in MODULES}
    names |= {f"campaign_{n}" for n in NAMED_CAMPAIGNS}
    return names


def check_artifacts() -> list[str]:
    """Validate ``results/BENCH_*.json`` against the bench registry.

    Returns human-readable problem strings (empty = clean).  Two
    failure classes, both of which have bitten before:

    * an artifact whose stem maps to no registered bench module or
      named campaign — a leftover from a deleted bench (the stale
      ``BENCH_chaos.json``) that CI can never refresh;
    * a bare ``NaN``/``Infinity`` literal, or a non-finite/null
      ``us_per_call`` without the explicit ``"status": "nan"``
      marker — a number downstream tooling would silently propagate.
    """
    known = _registered_artifact_names()
    problems: list[str] = []

    def _reject(const: str):
        raise ValueError(f"bare {const} literal")

    for path in sorted(glob.glob(os.path.join(RESULTS_DIR,
                                              "BENCH_*.json"))):
        base = os.path.basename(path)
        stem = base[len("BENCH_"):-len(".json")]
        if stem not in known:
            problems.append(
                f"{base}: no registered bench module or named campaign "
                f"produces it (stale artifact — delete it)")
            continue
        try:
            with open(path) as f:
                payload = json.load(f, parse_constant=_reject)
        except ValueError as e:
            problems.append(f"{base}: invalid JSON ({e})")
            continue
        for n, cell in payload.get("cells", {}).items():
            us = cell.get("us_per_call")
            bad = us is None or (isinstance(us, float)
                                 and not math.isfinite(us))
            if bad and cell.get("status") != "nan":
                problems.append(
                    f"{base}: cell {n!r} has non-finite us_per_call "
                    f"without the explicit 'status': 'nan' marker")
    return problems

#: --campaign demo: a small paper-style grid (Fig 6 slice + tenants),
#: including one overflow-regime cell (the dts/4-consumer cell gets a
#: tight 256-message queue cap + the overflow stress knobs, so the
#: demo exercises the lane-resolved stacked flow-control path — the
#: grid's per-queue volume is 2048/2 = 1024 messages, well past the
#: cap, and the 3 seed lanes stack through one batched run)
DEMO_CAMPAIGN = {
    "name": "demo",
    "patterns": ["feedback"],
    "architectures": ["dts", "mss"],
    "workloads": ["dstream"],
    "consumers": [4, 8],
    "n_runs": 3,
    "total_messages": 2048,
    "cell_params": [
        [{"arch": "dts", "n_consumers": 4},
         {"confirm_window": 64, "prefetch": 16, "ack_batch": 4,
          "consumer_proc_s": 2e-3, "queue_max_bytes": 256 * 16384}],
    ],
}


#: named campaign specs runnable as --campaign <name>
NAMED_CAMPAIGNS = {
    "demo": lambda: DEMO_CAMPAIGN,
    # the §6 deployment-feasibility grid (three archs x tenant sweep)
    "deployment": lambda: bench_deployment_feasibility.DEPLOYMENT_CAMPAIGN,
}


def run_campaign_cli(args, cache: Cache) -> None:
    from repro.core.campaign import CampaignSpec, run_campaign
    if args.campaign in NAMED_CAMPAIGNS:
        spec = CampaignSpec.from_json(
            json.dumps(NAMED_CAMPAIGNS[args.campaign]()))
    else:
        with open(args.campaign) as f:
            spec = CampaignSpec.from_json(f.read())
    if args.engine is not None:
        # the --engine escape hatch applies to campaign cells too
        # (explicit per-spec params win)
        spec.params.setdefault("engine", args.engine)
        if args.engine == "jax":
            # opt the grid into the whole-run device program; cells
            # outside its validated regime fall back per cell (the
            # fallback is counted in the campaign result JSON)
            spec.params.setdefault("jax_device_loop", True)
    res = run_campaign(spec, cache=cache, workers=args.workers,
                       progress=lambda m: print(f"# {m}", file=sys.stderr))
    out = args.campaign_out or os.path.join(
        os.path.dirname(__file__), "..", "results",
        f"campaign_{spec.name}.json")
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        f.write(res.to_json())
    cache.save()
    print(f"# campaign {spec.name!r}: {len(res.cells)} cells "
          f"({res.n_cached} cached) in {res.wall_s:.1f}s -> {out}",
          file=sys.stderr)
    print("name,us_per_call,derived")
    rows = []
    for s in res.averaged:
        us = (1e6 / s.throughput_msgs_s if s.feasible
              and s.throughput_msgs_s else float("nan"))
        tenant_tag = f"/t{s.tenants}" if s.tenants > 1 else ""
        name = (f"campaign/{spec.name}/{s.pattern}/{s.arch}/{s.workload}/"
                f"c{s.n_consumers}{tenant_tag}")
        derived = (f"thr={s.throughput_msgs_s:.0f}msg/s "
                   f"n_runs={s.n_runs} engine={s.engine}")
        print(f"{name},{us:.1f},{derived}")
        rows.append((name, us, derived))
    jpath = write_bench_json(f"campaign_{spec.name}", rows, res.wall_s)
    print(f"# wrote {jpath}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("only", nargs="?", default=None,
                    help="run a single module (e.g. fig4, campaign)")
    ap.add_argument("--engine", choices=("heap", "vectorized", "jax"),
                    default=None,
                    help="StreamSim backend for simulator cells "
                         "(default: the SimParams default, vectorized); "
                         "'jax' falls back per cell when jax is missing")
    ap.add_argument("--campaign", default=None, metavar="SPEC",
                    help="execute a campaign grid: path to a "
                         "CampaignSpec JSON file, or a named grid "
                         "('demo', 'deployment')")
    ap.add_argument("--campaign-out", default=None, metavar="PATH",
                    help="where to write the campaign results JSON "
                         "(default results/campaign_<name>.json)")
    ap.add_argument("--workers", type=int, default=None,
                    help="campaign process fan-out (default: one per "
                         "CPU, capped by the group count; a grid with "
                         "jax cells runs in-process)")
    ap.add_argument("--check-artifacts", action="store_true",
                    help="validate results/BENCH_*.json against the "
                         "bench registry (stale artifacts, bare NaN) "
                         "and exit")
    args = ap.parse_args()
    if args.check_artifacts:
        problems = check_artifacts()
        for p in problems:
            print(f"ARTIFACT: {p}", file=sys.stderr)
        if problems:
            raise SystemExit(1)
        print("# artifacts OK", file=sys.stderr)
        return
    if args.campaign and args.only:
        ap.error("--campaign replaces the bench modules; drop the "
                 f"positional module argument {args.only!r}")
    common.DEFAULT_ENGINE = args.engine
    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    try:
        cache = Cache()
    except LegacyCacheError as e:
        print(f"FATAL: {e}", file=sys.stderr)
        raise SystemExit(2)
    if args.campaign:
        run_campaign_cli(args, cache)
        return
    print("name,us_per_call,derived")
    for name, mod in MODULES:
        if args.only and args.only != name:
            continue
        t0 = time.time()
        rows = [tuple(row) for row in mod.run(cache)]
        for n, us, derived in rows:
            print(f"{n},{us:.1f},{derived}")
        wall = time.time() - t0
        jpath = write_bench_json(name, rows, wall)
        print(f"# {name} finished in {wall:.1f}s -> {jpath}",
              file=sys.stderr)
    cache.save()


if __name__ == "__main__":
    main()
