"""Shared benchmark machinery: disk-cached simulator runs + CSV rows.

Row contract (benchmarks/run.py prints ``name,us_per_call,derived``):
  name        - benchmark cell id
  us_per_call - microseconds per *message* (1e6 / throughput) for
                throughput cells, or median RTT in us for latency cells
  derived     - paper reference value + deviation, or the measured
                secondary quantity

Cache keys are versioned and carry the *engine name*, the execution
path the cell resolves to (``RunResult.path``) and a fingerprint of the
fully-resolved :class:`SimParams` (defaults + overrides), so an engine
or path switch or a simulator-default change can never silently serve
stale numbers.  Legacy-format keys (pre-``v2|``) make the cache fail
loudly — see :class:`Cache`.
"""

from __future__ import annotations

import json
import os
from typing import Callable, Optional

from repro.core.campaign import CACHE_KEY_VERSION
from repro.core.campaign import params_fingerprint as _params_fingerprint
from repro.core.metrics import rtt_fraction_under, summarize
from repro.core.patterns import run_pattern
from repro.core.simulator import SimParams

CACHE_PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                          "bench_cache.json")

# CACHE_KEY_VERSION (re-exported above from repro.core.campaign, the
# single definition): every cache key must start with it; anything else
# is a legacy key from before engine/params-aware keying and must not
# be served.

#: process-wide engine override (benchmarks/run.py --engine); None means
#: "whatever SimParams defaults to"
DEFAULT_ENGINE: Optional[str] = None


def resolve_engine(engine: Optional[str] = None, spec=None) -> str:
    """Effective engine for a benchmark cell: explicit argument, then the
    --engine override, then the SimParams default.

    When the cell's :class:`ExperimentSpec` is known, pass it: a
    requested ``jax`` cell that ``jax_supported`` rejects actually runs
    vectorized (the ``run_many`` fallback), and its cache key must say
    so — keying on the requested engine would serve those vectorized
    numbers to a later genuinely-jax run (cache poisoning)."""
    if engine is not None:
        eng = engine
    elif DEFAULT_ENGINE is not None:
        eng = DEFAULT_ENGINE
    else:
        eng = SimParams().engine
    if eng == "jax" and spec is not None:
        import dataclasses

        from repro.core.campaign import resolved_engine
        if spec.params.engine != eng:
            spec = dataclasses.replace(
                spec, params=dataclasses.replace(spec.params, engine=eng))
        eng = resolved_engine(spec)
    return eng


def resolve_path(engine: str, spec=None, **params) -> str:
    """Execution path (``RunResult.path``) of a cell on the resolved
    ``engine``.  Only a jax cell has more than one: without its spec it
    runs on the vectorized engine, and a cell requesting the whole-run
    device program must pass its spec so the regime gate can be
    consulted."""
    if engine != "jax":
        return engine
    if spec is None:
        if params.get("jax_device_loop"):
            raise ValueError("a jax cell requesting jax_device_loop needs "
                             "its spec to resolve its execution path")
        return "vectorized"
    import dataclasses

    from repro.core.campaign import resolved_path
    return resolved_path(dataclasses.replace(
        spec, params=dataclasses.replace(spec.params, engine=engine)))


def params_fingerprint(engine: str, **params) -> str:
    """Short stable hash of the fully-resolved SimParams for a cell.

    Built from the constructed dataclass (defaults + overrides) with
    the one shared fingerprint construction
    (``repro.core.campaign.params_fingerprint``), so any change to
    simulator defaults — not just the overrides a bench passes —
    invalidates the cache entry, for bench and campaign cells alike."""
    return _params_fingerprint(SimParams(engine=engine, **params))


def cache_key(name: str, engine: Optional[str] = None, spec=None,
              **params) -> str:
    """Versioned cache key:
    ``v2|engine=<engine>|path=<path>|p=<fingerprint>|<name>``.

    Use for every cell whose value depends on a simulator run; cells with
    no simulator dependence may use :func:`plain_key`.  Pass ``spec``
    (the cell's :class:`ExperimentSpec`) whenever it is known so the key
    carries the *resolved* engine and path — see :func:`resolve_engine`
    and :func:`resolve_path`."""
    eng = resolve_engine(engine, spec=spec)
    path = resolve_path(eng, spec, **params)
    return (f"{CACHE_KEY_VERSION}|engine={eng}|path={path}|"
            f"p={params_fingerprint(eng, **params)}|{name}")


def plain_key(name: str) -> str:
    """Versioned key for cells with no simulator dependence (kernels)."""
    return f"{CACHE_KEY_VERSION}|{name}"


class LegacyCacheError(RuntimeError):
    pass


class Cache:
    """Disk-backed benchmark cache.

    Refuses to operate on a cache file containing legacy-format keys
    (anything not ``v2|``-prefixed): those entries predate engine- and
    params-aware keying, so serving them after an engine change would
    silently report stale heap-engine numbers.  Delete the file (or the
    offending entries) to proceed — the bench runner re-measures."""

    def __init__(self, path: str = CACHE_PATH):
        self.path = os.path.abspath(path)
        self.data: dict = {}
        if os.path.exists(self.path):
            with open(self.path) as f:
                self.data = json.load(f)
            legacy = [k for k in self.data
                      if not k.startswith(f"{CACHE_KEY_VERSION}|")]
            if legacy:
                raise LegacyCacheError(
                    f"{self.path} contains {len(legacy)} legacy-format "
                    f"cache key(s) (e.g. {legacy[0]!r}) from before "
                    f"engine/params-aware keying; serving them could "
                    f"return stale numbers for the wrong engine. Delete "
                    f"the file and re-run to re-measure.")

    def get_or(self, key: str, fn: Callable[[], dict]) -> dict:
        if not key.startswith(f"{CACHE_KEY_VERSION}|"):
            raise LegacyCacheError(
                f"cache key {key!r} lacks the {CACHE_KEY_VERSION}| "
                f"version prefix; build it with cache_key()/plain_key()")
        if key not in self.data:
            self.data[key] = fn()
            self.save()
        return self.data[key]

    def save(self) -> None:
        os.makedirs(os.path.dirname(self.path), exist_ok=True)
        with open(self.path, "w") as f:
            json.dump(self.data, f, indent=1)


def sim_cell(cache: Cache, pattern: str, arch: str, workload: str,
             nc: int, msgs: int, n_runs: int = 1,
             engine: Optional[str] = None, **params) -> dict:
    from repro.core.patterns import pattern_spec
    rep = pattern_spec(pattern, arch, workload, nc, total_messages=msgs,
                       engine=resolve_engine(engine), **params)
    eng = resolve_engine(engine, spec=rep)
    key = cache_key(f"{pattern}|{arch}|{workload}|{nc}|{msgs}|{n_runs}",
                    engine=eng, spec=rep, **params)

    def compute() -> dict:
        rs = run_pattern(pattern, arch, workload, nc, total_messages=msgs,
                         n_runs=n_runs, engine=eng, **params)
        r = rs[0]
        if not r.feasible:
            return {"feasible": False, "reason": r.infeasible_reason}
        s = summarize(r)
        import numpy as np
        meds = [summarize(x).median_rtt_s for x in rs]
        thrs = [summarize(x).throughput_msgs_s for x in rs]
        return {
            "feasible": True,
            "throughput": float(np.nanmean(thrs)),
            "median_rtt": float(np.nanmean(meds)) if r.rtts.size else None,
            "min_rtt": s.min_rtt_s if r.rtts.size else None,
            "p95_rtt": s.p95_rtt_s if r.rtts.size else None,
            "frac_under": {
                str(t): rtt_fraction_under(r, t)
                for t in (0.7, 5.0, 12.5)} if r.rtts.size else None,
            "goodput_gbps": s.goodput_gbps,
            "rejected": s.rejected,
            "blocked": s.blocked,
        }

    return cache.get_or(key, compute)


def thr_row(name: str, cell: dict, paper: float | None = None):
    if not cell.get("feasible"):
        return (name, float("nan"), "INFEASIBLE:" + cell.get("reason", "")[:40])
    t = cell["throughput"]
    us = 1e6 / t if t else float("nan")
    if paper:
        dev = 100.0 * (t - paper) / paper
        return (name, us, f"thr={t:.0f}msg/s paper={paper:.0f} dev={dev:+.0f}%")
    return (name, us, f"thr={t:.0f}msg/s")


def rtt_row(name: str, cell: dict, paper_s: float | None = None):
    if not cell.get("feasible"):
        return (name, float("nan"), "INFEASIBLE")
    m = cell["median_rtt"]
    if paper_s:
        dev = 100.0 * (m - paper_s) / paper_s
        return (name, m * 1e6, f"rtt={m * 1e3:.0f}ms paper={paper_s * 1e3:.0f}ms dev={dev:+.0f}%")
    return (name, m * 1e6, f"rtt={m * 1e3:.0f}ms")
