"""JAX engine benchmark: the whole-run device program against the
vectorized engine on the deployment grid.

``jaxeng/e2e/*`` rows run whole deployment-grid cells through
``run_many`` on ``engine="jax"`` (with the whole-run device program
requested via ``jax_device_loop=True``; see
:mod:`repro.core.jax_device_loop`) vs ``engine="vectorized"``,
wall-clock + throughput parity in 'derived'.  These rows **assert the
>= 1x end-to-end gate**: with the cohort event loop lifted into one
``lax.scan`` device program the jax engine must at least match the
NumPy engine's wall clock on cells the wave model supports (jit compile
time is reported separately and never counted — the compiled program
is shape-bucketed and amortizes across a campaign).  Throughput parity
is asserted at the ``device_loop.all.throughput`` band from
:mod:`repro.core.parity`.

``JAX_BENCH_SMOKE=1`` shrinks the e2e grid for CI.  Without jax
importable, every row degrades to ``SKIPPED:no-jax`` instead of failing
(mirroring ``run_many``'s per-cell fallback).
"""

from __future__ import annotations

import os
import time

from benchmarks.common import Cache, cache_key
from repro.core.jax_engine import jax_available
from repro.core.metrics import summarize
from repro.core.simulator import ExperimentSpec, SimParams
from repro.core.workloads import DSTREAM

SMOKE = os.environ.get("JAX_BENCH_SMOKE") == "1"

#: the >= 1x end-to-end gate: the device-programmed jax engine must not
#: lose to the NumPy cohort loop on wave-supported deployment cells
#: (compile excluded)
E2E_SPEEDUP_GATE = 1.0

E2E_SEEDS = (0, 1000, 2000)
E2E_MSGS = 256 if SMOKE else 2048
E2E_ARCHS = ("mss",) if SMOKE else ("dts", "prs-haproxy", "mss")
E2E_TENANTS = 4


def _e2e_specs(arch: str, engine: str) -> list:
    # work_sharing is the wave model's broadly-validated regime (the
    # feedback corridor is narrow; see _device_loop_ok) — these cells
    # sit squarely inside it at the full deployment-grid scale
    device = True if engine == "jax" else None
    return [ExperimentSpec(
        pattern="work_sharing", workload=DSTREAM, arch=arch,
        n_producers=16, n_consumers=16, total_messages=E2E_MSGS,
        params=SimParams(seed=s, engine=engine, jax_device_loop=device),
        tenants=E2E_TENANTS, tenant_isolation="vhost")
        for s in E2E_SEEDS]


def _e2e_cell(arch: str) -> dict:
    from repro.core.parity import band
    from repro.core.vectorized import run_many
    out = {}
    # first jax call jit-compiles the device program for this shape
    # bucket; time it separately so the gate measures the amortized
    # cost a campaign actually pays
    t0 = time.perf_counter()
    run_many(_e2e_specs(arch, "jax"))
    compile_s = time.perf_counter() - t0
    for engine in ("vectorized", "jax"):
        t0 = time.perf_counter()
        rs = run_many(_e2e_specs(arch, engine))
        wall = time.perf_counter() - t0
        s = summarize(rs[0])
        out[engine] = {"wall_s": wall, "thr": s.throughput_msgs_s,
                       "rtt": s.median_rtt_s, "ran_on": s.engine}
    v, j = out["vectorized"], out["jax"]
    out["thr_dev"] = abs(j["thr"] - v["thr"]) / v["thr"]
    out["compile_s"] = compile_s
    out["speedup"] = v["wall_s"] / j["wall_s"]
    assert out["thr_dev"] <= band("device_loop.all.throughput"), (
        f"e2e {arch}: device-program throughput deviates "
        f"{100 * out['thr_dev']:.2f}% from the vectorized engine, "
        f"outside the device_loop.all.throughput band")
    assert out["speedup"] >= E2E_SPEEDUP_GATE, (
        f"e2e {arch}: jax engine (device program) {j['wall_s']:.2f}s "
        f"vs vectorized {v['wall_s']:.2f}s — speedup "
        f"{out['speedup']:.2f}x < {E2E_SPEEDUP_GATE}x gate")
    return out


def run(cache: Cache):
    rows = []
    if not jax_available():
        for arch in E2E_ARCHS:
            rows.append((f"jaxeng/e2e/{arch}/t{E2E_TENANTS}",
                         float("nan"), "SKIPPED:no-jax"))
        return rows

    for arch in E2E_ARCHS:
        c = cache.get_or(
            cache_key(f"jaxeng|e2e|ws-dev|{arch}|t{E2E_TENANTS}"
                      f"|m{E2E_MSGS}", engine="jax",
                      spec=_e2e_specs(arch, "jax")[0]),
            lambda arch=arch: _e2e_cell(arch))
        v, j = c["vectorized"], c["jax"]
        rows.append((
            f"jaxeng/e2e/{arch}/t{E2E_TENANTS}",
            1e6 / j["thr"] if j["thr"] else float("nan"),
            f"speedup={c.get('speedup', float('nan')):.1f}x "
            f"(gate>={E2E_SPEEDUP_GATE}x) "
            f"thr_dev={100 * c['thr_dev']:.2f}% "
            f"wall_vec={v['wall_s']:.1f}s wall_jax={j['wall_s']:.1f}s "
            f"compile={c.get('compile_s', float('nan')):.1f}s "
            f"ran_on={j['ran_on']}"))
    return rows
