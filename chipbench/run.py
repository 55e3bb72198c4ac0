"""On-chip benchmark of StreamSim's whole-run device program.

Run from the root of a checkout, on a machine with a TPU::

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration
(``chipbench/configs/<name>.json``) and a traffic mix
(``chipbench/traffic/<name>.json``).  One client sends simulation
requests in a closed loop: each request is one ``run_many`` call on
``engine="jax", jax_device_loop=True`` specs, up to the summaries of
its results.  Set-up warms up the requests' shape; then requests run
back to back until ``--seconds`` have passed, and the window ends when
the last of them returns.

``--trace 0`` reports the cell's end-to-end metrics.  ``--trace 1``
wraps the layer functions of ``repro.core.jax_device_loop`` in host
timers and profiler annotations, profiles ``PROFILED_REQUESTS`` requests
between set-up and the window, and reports
the per-layer metrics that the readers in ``chipbench/layers/<metric>.py``
take from the trace and from the window's spans.

After the window, a sample of requests drawn from ``--seed`` is checked
against the plain reference (``chipbench/check.py``).  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last the compared numbers with their limits under ``checks``.  The
run exits non-zero, printing no result, without a TPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Callable, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from chipbench import check, generator, trace, work  # noqa: E402

#: the layer functions the traced run times, in repro.core.jax_device_loop
SPANS = ("build_static", "draw_jitter", "_run_program", "_assemble")
#: requests set-up sends (every request of a mix has the same shape)
WARMUP_REQUESTS = 2
#: requests the traced run profiles
PROFILED_REQUESTS = 2
#: requests checked against the reference
SAMPLE = 2
#: a request over this many times the median wall time is a stall
STALL = 1.25
#: JAX events that mean something was traced or compiled
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    end_to_end: list
    per_layer: list


def load_cell(root: str, name: str) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg_file = {c["name"]: c["file"] for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_file)) as f:
        cfg = json.load(f)

    def mine(m: dict) -> bool:
        return name in m.get("workloads", [name])
    return Cell(name=name, chips=int(w["chips"]), cfg=cfg,
                mix=generator.load(root, w["traffic"]),
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


def device_check(chips: int) -> tuple[str, str, int]:
    """(platform, device_kind, device count); exits unless on a TPU with
    at least ``chips`` chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: JAX's first device is "
                         f"{devs[0].platform!r}, not a TPU; not measuring")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"sees {len(devs)}")
    return devs[0].platform, devs[0].device_kind, len(devs)


def compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at ``<root>/.jax_cache``,
    holding every program, however fast it compiled."""
    import jax
    path = os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def memory_peak_bytes(chips: int) -> int:
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def load_reader(root: str, metric: str) -> Callable:
    path = os.path.join(root, "chipbench", "layers", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_layer_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


#: calibration the program fixes outside ``Calibration``, read from the
#: architecture it builds: key -> (architecture, reader)
BUILT_CONSTANTS = {
    "control_latency_s": ("dts", lambda a: a.control_latency_s()),
    "lb_servers": ("mss", lambda a: a.resources["lb"].servers),
    "lb_service_s": ("mss", lambda a: a.resources["lb"].service_s),
    "proxy_servers": ("prs-haproxy", lambda a: a.resources["pproxy"].servers),
}


def calibration_gaps(cfg: dict) -> list:
    """``[(key, file's value, program's value)]`` where the configuration
    states a calibration that the program does not run.  ``run_many``
    takes no calibration, so the file has to agree with the program's
    own; the inventory, workload and params are handed over instead."""
    from repro.core.architectures import Calibration, make_architecture
    from repro.core.ds2hpc import ClusterInventory
    prog = dataclasses.asdict(Calibration())
    inv = ClusterInventory(**cfg["inventory"])
    gaps = []
    for key, want in cfg["calibration"].items():
        if key in prog:
            have = prog[key]
        elif key in BUILT_CONSTANTS:
            arch, read = BUILT_CONSTANTS[key]
            have = read(make_architecture(arch, inv))
        else:
            have = None
        if have != want:
            gaps.append((key, want, have))
    return gaps


class Client:
    """Builds the specs of a request from the configuration's file and
    sends it through ``run_many``."""

    def __init__(self, cfg: dict) -> None:
        from repro.core.ds2hpc import ClusterInventory
        from repro.core.metrics import summarize
        from repro.core.simulator import ExperimentSpec, SimParams
        from repro.core.vectorized import run_many
        from repro.core.workloads import get_workload
        gaps = calibration_gaps(cfg)
        if gaps:
            raise SystemExit(
                f"chipbench: configuration {cfg['name']} states calibration "
                f"the program does not run (key, file, program): {gaps}")
        self.cfg = cfg
        self._summarize, self._run_many = summarize, run_many
        self._spec, self._params = ExperimentSpec, SimParams
        self._inventory = ClusterInventory(**cfg["inventory"])
        wl = dict(cfg["workload"])
        self._workload = dataclasses.replace(get_workload(wl.pop("name")),
                                             **wl)

    def specs(self, request: list) -> list:
        c = self.cfg
        return [self._spec(
            pattern=c["pattern"], workload=self._workload, arch=arch,
            n_producers=c["n_producers"], n_consumers=c["n_consumers"],
            total_messages=c["total_messages"],
            params=self._params(seed=s, engine="jax", jax_device_loop=True,
                                **c["params"]),
            tenants=c["tenants"], tenant_isolation=c["tenant_isolation"])
            for arch, seeds in request for s in seeds]

    def send(self, specs: list) -> tuple[list, list]:
        results = self._run_many(specs, inventory=self._inventory)
        return results, [self._summarize(r) for r in results]


class GcClock:
    """Seconds the garbage collector ran, and its full (generation 2)
    passes, since the last ``take``."""

    def __init__(self) -> None:
        self.s, self.full, self._t0 = 0.0, 0, 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.s += time.perf_counter() - self._t0
            self.full += info["generation"] == 2

    def take(self) -> tuple[float, int]:
        out = (self.s, self.full)
        self.s, self.full = 0.0, 0
        return out

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)


class Spans:
    """Host timers and profiler annotations around the layer functions;
    a missing function is an error, never a zero."""

    def __init__(self) -> None:
        self.current: dict = {}

    def install(self) -> None:
        import jax
        from repro.core import jax_device_loop as dl
        for name in SPANS:
            fn = getattr(dl, name)

            def timed(*a: Any, _fn: Callable = fn, _name: str = name,
                      **kw: Any) -> Any:
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation(_name):
                    out = _fn(*a, **kw)
                self.current[_name] = (self.current.get(_name, 0.0)
                                       + time.perf_counter() - t0)
                return out
            setattr(dl, name, timed)

    def take(self) -> dict:
        out, self.current = self.current, {}
        return out


class Sample:
    """A seeded reservoir of ``SAMPLE - 1`` requests plus the one whose
    wall time was longest."""

    def __init__(self, seed: int) -> None:
        self.rng = np.random.default_rng([int(seed) & (2 ** 64 - 1), 2])
        self.n, self.kept, self.top = 0, [], (-1.0, None)

    def offer(self, request: list, results: list, summaries: list,
              wall: float) -> None:
        item = (request, results, [s.throughput_msgs_s for s in summaries])
        self.n += 1
        if wall > self.top[0]:
            self.top = (wall, item)
        if len(self.kept) < SAMPLE - 1:
            self.kept.append(item)
        else:
            j = int(self.rng.integers(self.n))
            if j < SAMPLE - 1:
                self.kept[j] = item

    def items(self) -> list:
        return [self.top[1]] + [it for it in self.kept
                                if it is not self.top[1]]


@dataclasses.dataclass
class Observed:
    """What the per-layer readers read."""

    walls: list              # seconds of each untraced request
    spans: list              # {span name: seconds} of each untraced request
    reduced: Optional[trace.Reduced]
    traced_bytes: float      # least bytes of the profiled requests
    peak: dict


def say_stalls(walls: list, gc_log: list) -> None:
    """The garbage collector's share of the window, and of its slow
    requests: those over ``STALL`` times the median wall time."""
    med = float(np.median(walls))
    slow = [(w, g) for w, g in zip(walls, gc_log) if w > STALL * med]
    say(f"gc in window: {sum(g[0] for g in gc_log)!r} s, "
        f"{sum(g[1] for g in gc_log)} full passes; {len(slow)} requests "
        f"over {STALL} x median, {sum(g[1] > 0 for _, g in slow)} of them "
        f"with a full pass; (wall s, gc s, full passes) of the slowest: "
        f"{sorted(((w, g[0], g[1]) for w, g in slow), reverse=True)[:8]!r}")


def run(argv: Optional[list] = None, root: str = ROOT) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(root, args.workload)
    platform, kind, count = device_check(cell.chips)
    say(f"device: platform={platform} kind={kind} count={count}")
    say(f"compile cache: {compile_cache(root)}")
    sys.path.insert(0, os.path.join(root, "src"))
    import jax
    cfg = cell.cfg
    ref = check.load_reference(root, cfg["pattern"])
    traffic = generator.Traffic(cell.mix, cfg["architectures"], args.seed)
    client = Client(cfg)
    spans = Spans()
    tracing = bool(args.trace)
    readers = {m["name"]: load_reader(root, m["name"])
               for m in cell.per_layer} if tracing else {}
    if tracing:
        spans.install()

    # set-up: requests of the window's shape, seeds off the window
    for k in range(WARMUP_REQUESTS):
        client.send(client.specs(traffic.request(k, generator.WARMUP)))
        say(f"warm-up request {k} done at {time.perf_counter() - T_START!r} s")
    spans.take()
    compiles = [0]

    def on_event(event: str, _secs: float, **_kw: Any) -> None:
        if event in COMPILE_EVENTS:
            compiles[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)

    sample = Sample(args.seed)
    tally = {"sent": 0, "consumed": 0, "off": 0, "lost": 0, "dup": 0,
             "failed": 0}

    def send(k: int) -> tuple[list, float]:
        request = traffic.request(k)
        specs = client.specs(request)
        ts = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace.REQUEST):
            results, summaries = client.send(specs)
        wall = time.perf_counter() - ts
        off, lost, dup = check.window_faults(cfg, results)
        tally["sent"] += 1
        tally["consumed"] += sum(r.n_consumed for r in results)
        tally["off"] += off
        tally["lost"] += lost
        tally["dup"] += dup
        tally["failed"] += bool(off or lost or dup)
        sample.offer(request, results, summaries, wall)
        return request, wall

    k, red, traced = 0, None, []
    if tracing:
        # the profiled requests run before the window, which then gives
        # the spans with the profiler off
        trace_dir = os.path.join(root, ".chipbench", "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        for k in range(PROFILED_REQUESTS):
            traced.append(send(k)[0])
        jax.profiler.stop_trace()
        k += 1
        spans.take()
        red = trace.reduce(trace.read_xplane(trace.find_xplane(trace_dir),
                                             SPANS))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if red is None:
            raise RuntimeError("the profiled requests ran no device operation")
    walls, span_log, gc_log = [], [], []
    gc_clock = GcClock()
    consumed0 = tally["consumed"]
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    t_end = t0
    while t_end - t0 < args.seconds:
        walls.append(send(k)[1])
        span_log.append(spans.take())
        gc_log.append(gc_clock.take())
        t_end = time.perf_counter()
        k += 1
    window_s = t_end - t0
    gc_clock.close()
    mem_peak = memory_peak_bytes(cell.chips)
    say(f"compilations in window: {compiles[0]}")
    say(f"window: {len(walls)} requests in {window_s!r} s, set-up "
        f"{setup_s!r} s; request wall min / median / max "
        f"{min(walls)!r} / {float(np.median(walls))!r} / {max(walls)!r} s")
    say_stalls(walls, gc_log)

    device = {"platform": platform, "kind": kind, "count": count,
              "memory_peak_bytes": mem_peak}
    out: dict = {"correct": False, "attempted": tally["sent"],
                 "failed": tally["failed"]}
    if tracing:
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        obs = Observed(
            walls=walls, spans=span_log,
            reduced=red, peak=work.peak(kind),
            traced_bytes=sum(work.least_bytes(cfg, ref, r) for r in traced))
        metrics = {}
        for m in cell.per_layer:
            v = readers[m["name"]](obs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": red.device_ops,
                            "idle_gaps": red.idle_gaps}
    else:
        e2e = {"sim_msgs_per_s": (tally["consumed"] - consumed0) / window_s,
               "request_p90_s": float(np.percentile(walls, 90)),
               "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    out["metrics"], out["device"] = metrics, device

    readings = check.sample_gaps(cfg, ref, sample.items())
    readings.update(off_device_path=tally["off"], lost_messages=tally["lost"],
                    duplicate_messages=tally["dup"])
    limits = dict(ref.LIMITS, off_device_path=0, lost_messages=0,
                  duplicate_messages=0)
    out["correct"], out["checks"] = check.verdict(readings, limits)
    for name, (v, lim) in out["checks"].items():
        say(f"check {name} = {v!r} (limit {lim!r})")
    return out


def main() -> None:
    out = run()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
