"""On-chip smoke run of the StreamSim JAX engine (``engine="jax"``).

Drives the JAX path once through the entry points users call
(``run_many``, ``run_pattern``) at the repo's own bench sizes, on one
TPU chip in one process, and checks every result against the repo's
reference engines:

* A — the whole-run device program on the deployment grid (the
  ``bench_jax_engine`` e2e shape): work_sharing, dstream, 16 producers x
  16 consumers, 4 vhost tenants, 2048 messages, on DTS, PRS-HAProxy and
  MSS, three seeds each;
* B — the device program at facility-fleet size: DTS, 256 producers x
  256 consumers, 65536 messages (256 per producer), three seeds;
* C — a Fig 7b cell the device program declines, requested with
  ``engine="jax", jax_device_loop=True``: broadcast_gather, MSS, generic
  workload, 32 consumers, 384 messages.  It runs on the vectorized
  engine on the host.

Every phase asserts the path each cell took (``RunResult.path``),
message conservation, and parity with the reference engine inside the
``repro.core.parity`` bands.  The wall seconds it prints are the chip
wall time of a smoke run, not benchmark metrics.  The last line of
standard output is a JSON object naming the device, printed only when
every phase passed.

Run from the repository root on a machine with a TPU::

    python chip_smoke.py

It exits non-zero, printing no result, when JAX's first device is not a
TPU.  The persistent compile cache is ``$JAX_COMPILATION_CACHE_DIR``
when that is set, else ``.jax_cache/`` beside this file.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Callable

ROOT = os.path.dirname(os.path.abspath(__file__))

SEEDS = (0, 1000, 2000)
#: phase A: the deployment grid (bench_jax_engine._e2e_specs)
GRID = dict(archs=("dts", "prs-haproxy", "mss"), clients=16, tenants=4,
            messages=2048)
#: phase B: bench_engine_scaling's 256-client fleet at 256 msgs/producer
FLEET = dict(archs=("dts",), clients=256, tenants=1, messages=65536)
#: phase C: a Fig 7b cell (bench_fig7_broadcast_gather)
COHORT = dict(arch="mss", consumers=32, messages=384)


def device_check() -> tuple[str, str, int]:
    """(platform, device_kind, device count); exits unless on a TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX's first device is "
                         f"{devs[0].platform!r}, not a TPU; not running")
    return devs[0].platform, devs[0].device_kind, len(devs)


def compile_cache() -> str:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compat import enable_compile_cache
    return enable_compile_cache(ROOT)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def timed(fn: Callable[[], Any]) -> tuple[Any, float]:
    """Result and wall seconds; every timed call returns host NumPy
    arrays, so the device work has finished when the clock stops."""
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def wall(phase: str, cold: float, warm: float) -> None:
    say(phase, f"chip wall time (smoke run, not a benchmark metric): "
               f"first call {cold:.3f} s (compiles), second call "
               f"{warm:.3f} s")


def rel(ref: float, got: float) -> float:
    return abs(got - ref) / ref


def device_phase(phase: str, archs: tuple, clients: int, tenants: int,
                 messages: int) -> None:
    """The whole-run device program through ``run_many``, against the
    vectorized engine on the same specs."""
    from repro.core.metrics import summarize
    from repro.core.parity import band
    from repro.core.simulator import ExperimentSpec, SimParams
    from repro.core.vectorized import run_many
    from repro.core.workloads import DSTREAM

    def specs(engine: str) -> list:
        return [ExperimentSpec(
            pattern="work_sharing", workload=DSTREAM, arch=arch,
            n_producers=clients, n_consumers=clients,
            total_messages=messages,
            params=SimParams(seed=s, engine=engine,
                             jax_device_loop=engine == "jax" or None),
            tenants=tenants,
            tenant_isolation="vhost" if tenants > 1 else "shared")
            for arch in archs for s in SEEDS]

    say(phase, f"work_sharing dstream {clients}x{clients} tenants="
               f"{tenants} messages={messages} archs={','.join(archs)} "
               f"seeds={SEEDS}")
    _, cold = timed(lambda: run_many(specs("jax")))
    got, warm = timed(lambda: run_many(specs("jax")))
    wall(phase, cold, warm)
    ref, host_s = timed(lambda: run_many(specs("vectorized")))
    say(phase, f"vectorized reference: host wall time {host_s:.3f} s")
    thr_band = band("device_loop.all.throughput")
    rtt_band = band("device_loop.all.median_rtt")
    for spec, r, v in zip(specs("jax"), got, ref):
        sj, sv = summarize(r), summarize(v)
        thr_dev = rel(sv.throughput_msgs_s, sj.throughput_msgs_s)
        line = (f"{spec.arch} seed={spec.params.seed} path={r.path} "
                f"consumed={r.n_consumed}/{spec.total_messages} "
                f"thr={sj.throughput_msgs_s:.6g} msg/s "
                f"thr_dev={thr_dev:.6f} (band {thr_band})")
        if v.rtts.size or r.rtts.size:
            rtt_dev = rel(sv.median_rtt_s, sj.median_rtt_s)
            line += f" rtt_dev={rtt_dev:.6f} (band {rtt_band})"
        else:
            rtt_dev = 0.0
            line += " rtt: none recorded (work_sharing has no replies)"
        say(phase, line)
        assert r.path == "jax-device", (phase, spec.arch, r.path)
        assert v.path == "vectorized", (phase, spec.arch, v.path)
        assert r.n_consumed == v.n_consumed == spec.total_messages, (
            phase, spec.arch, r.n_consumed, v.n_consumed)
        assert thr_dev <= thr_band, (phase, spec.arch, thr_dev)
        assert rtt_dev <= rtt_band, (phase, spec.arch, rtt_dev)


def oracle_diff(phase: str, arch: str, clients: int, tenants: int,
                messages: int) -> None:
    """Largest absolute difference between the device trace and the
    NumPy-mirror oracle (``backend="numpy"``) on one stacked cell."""
    import numpy as np

    from repro.core import jax_device_loop as dl
    from repro.core.jax_engine import JaxStreamSim
    from repro.core.simulator import ExperimentSpec, SimParams
    from repro.core.workloads import DSTREAM
    spec = ExperimentSpec(
        pattern="work_sharing", workload=DSTREAM, arch=arch,
        n_producers=clients, n_consumers=clients, total_messages=messages,
        params=SimParams(seed=SEEDS[0], engine="jax", jax_device_loop=True),
        tenants=tenants, tenant_isolation="vhost" if tenants > 1 else "shared")
    sim = JaxStreamSim(spec, stack_seeds=list(SEEDS))
    ws = dl.build_static(sim)
    jit = dl.draw_jitter(sim, ws)
    yj = dl.run_wave_trace(ws, jit, backend="jax")
    yn = dl.run_wave_trace(ws, jit, backend="numpy")
    worst, mismatched = 0.0, 0
    for k in sorted(yn):
        a, b = np.asarray(yj[k]), np.asarray(yn[k])
        fin = np.isfinite(a) & np.isfinite(b)
        mismatched += int((np.isfinite(a) != np.isfinite(b)).sum())
        if fin.any():
            worst = max(worst, float(np.abs(a[fin] - b[fin]).max()))
    say(phase, f"{arch} device trace vs NumPy-mirror oracle: max abs "
               f"diff {worst!r} s over {sum(v.size for v in yn.values())} "
               f"trace values, {mismatched} finite/inf mismatches "
               f"(the CPU property test holds 1e-12)")


def cohort_phase(phase: str, arch: str, consumers: int,
                 messages: int) -> None:
    """A jax cell the device program declines, through ``run_pattern``:
    it runs on the vectorized engine, checked against the heap
    reference."""
    from repro.core.jax_device_loop import device_loop_supported
    from repro.core.metrics import summarize
    from repro.core.parity import band
    from repro.core.patterns import pattern_spec, run_pattern
    cell = ("broadcast_gather", arch, "generic", consumers)
    kw = dict(total_messages=messages, seed=0, jitter=0.0,
              jax_device_loop=True)
    ok, why = device_loop_supported(pattern_spec(*cell, engine="jax", **kw))
    say(phase, f"broadcast_gather {arch} generic consumers={consumers} "
               f"messages={messages}; device program declines: {why}")
    assert not ok, phase

    def run(engine: str) -> Any:
        return run_pattern(*cell, n_runs=1, engine=engine, **kw)[0]

    r, jax_s = timed(lambda: run("jax"))
    say(phase, f"jax request: host wall time {jax_s:.3f} s")
    h, host_s = timed(lambda: run("heap"))
    say(phase, f"heap reference: host wall time {host_s:.3f} s")
    sj, sh = summarize(r), summarize(h)
    thr_dev = rel(sh.throughput_msgs_s, sj.throughput_msgs_s)
    rtt_dev = rel(sh.median_rtt_s, sj.median_rtt_s)
    thr_band = band("broadcast_gather.all.throughput")
    rtt_band = band(f"broadcast_gather.{arch}.gather_rtt")
    expect = messages * consumers
    say(phase, f"path={r.path} consumed={r.n_consumed}/{expect} "
               f"thr_dev={thr_dev:.6f} (band {thr_band}) "
               f"gather_rtt_dev={rtt_dev:.6f} (band {rtt_band})")
    assert r.path == "vectorized", (phase, r.path)
    assert r.n_consumed == h.n_consumed == expect, (phase, r.n_consumed)
    assert thr_dev <= thr_band, (phase, thr_dev)
    assert rtt_dev <= rtt_band, (phase, rtt_dev)


def main() -> None:
    platform, kind, count = device_check()
    print(f"device: platform={platform} kind={kind} count={count}",
          flush=True)
    say("setup", f"compile cache: {compile_cache()}")
    device_phase("A", **GRID)
    oracle_diff("A", GRID["archs"][0], GRID["clients"], GRID["tenants"],
                GRID["messages"])
    device_phase("B", **FLEET)
    cohort_phase("C", **COHORT)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}))


if __name__ == "__main__":
    main()
