"""The program's own spans and named scopes in a profiler trace.

Where the chip sits idle, by the program's spans, and where the scan
step spends its device time, by named scope, for a few profiled
requests of a benchmark cell::

    python3 chipbench/program_trace.py --workload <cell> --seed <n>

It runs on a TPU, apart from the benchmark's run: set-up sends two
requests, then ``REQUESTS`` requests are profiled with the program's
recorder (``repro.core.spans``) on.  It prints, in seconds and as
shares:

* ``idle_by_span``: each nanosecond of device idle in the profiled
  window goes to the innermost ``streamsim.*`` span open at that
  instant, or to ``other host`` where none is; a gap is split at every
  span boundary inside it;
* ``scan_phases``: device time of the leaf operations (not the loops
  and conditionals that overlap their bodies) per named scope of the
  scan step, from the ``op_name`` metadata of each program's compiled
  HLO (the trace does not carry it), ``unscoped`` for the rest;
* the clock: how far each recorded span lies from its own annotation
  in the trace, after one offset taken from the first matched span.

The functions below also serve ``chipbench/tests/capture_scoped.py``,
which wrote the tests' trace fixture with them.
"""

from __future__ import annotations

import argparse
import dataclasses
import gzip
import os
import re
import shutil
import sys
from typing import Any, Callable, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import trace  # noqa: E402

OTHER = trace.OTHER
#: prefix of the program's own span names (``repro.core.spans``)
SPAN_PREFIX = "streamsim."
#: the named scopes of the scan step, and the share of ops outside them
SCOPES = ("publish_gate", "pump_assign", "transit", "consume_ack",
          "reply_pump", "reply_ack")
UNSCOPED = "unscoped"
#: ops whose interval covers the ops of their bodies
NOT_LEAF = ("while", "conditional", "call")
#: requests profiled
REQUESTS = 2


@dataclasses.dataclass
class ProgramTrace:
    """What a trace holds of the program's own work."""

    spans: list      # [(name, start_ns, end_ns)] of ``streamsim.*`` spans
    modules: list    # [(device, name, start_ns, end_ns)] program runs


def read_program(path: str) -> ProgramTrace:
    """The program's spans (host annotations named ``streamsim.*``) and
    each program execution (device line ``XLA Modules``) of one
    ``.xplane.pb`` (gzipped if ``.gz``)."""
    from jax.profiler import ProfileData
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    spans, mods, n_dev = [], [], 0
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            if "XLA Modules" in lines:
                mods += [(n_dev, e.name, e.start_ns,
                          e.start_ns + e.duration_ns)
                         for e in lines["XLA Modules"].events]
            n_dev += 1
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return ProgramTrace(spans=spans, modules=mods)


def window_holes(tr: trace.Trace) -> list:
    """Sorted holes in the union of device 0's ops over the span of the
    request annotations."""
    reqs = [(s, e) for n, s, e in tr.annotations if n == trace.REQUEST]
    if not reqs:
        return []
    lo, hi = min(s for s, _ in reqs), max(e for _, e in reqs)
    busy = trace.union(trace.clip([(s, e) for d, _, s, e in tr.ops
                                   if d == 0], lo, hi))
    return trace.gaps(busy, lo, hi)


def idle_by_span(holes: list, spans: list) -> dict:
    """Seconds of the ``holes`` (sorted, disjoint) per innermost span of
    ``spans`` open at each instant, ``OTHER`` where none is: a hole is
    split at every span boundary inside it."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    # the innermost (latest opened; spans nest) span of each piece
    # between two cuts
    pieces = []
    for lo, hi in zip(cuts, cuts[1:]):
        open_ = [(s, n) for n, s, e in spans if s <= lo and hi <= e]
        pieces.append((lo, hi, max(open_)[1] if open_ else OTHER))
    out: dict = {}
    j = 0
    for s, e in holes:
        cur = s
        while j < len(pieces) and pieces[j][1] <= cur:
            j += 1
        k = j
        while cur < e:
            if k < len(pieces) and pieces[k][0] <= cur:
                lo, hi, name = pieces[k]
                k += 1
            else:
                hi = pieces[k][0] if k < len(pieces) else e
                name = OTHER
            end = min(hi, e)
            out[name] = out.get(name, 0.0) + (end - cur) * 1e-9
            cur = end
    return out


def match_spans(mine: list, theirs: list) -> list:
    """Pairs ``(mine[i], theirs[j])`` of ``(name, start_ns, end_ns)``
    spans, the n-th of a name in start order with the n-th of the same
    name, in the order of ``mine``'s starts."""
    by_name: dict = {}
    for sp in sorted(theirs, key=lambda x: x[1]):
        by_name.setdefault(sp[0], []).append(sp)
    seen: dict = {}
    pairs = []
    for sp in sorted(mine, key=lambda x: x[1]):
        n = seen.get(sp[0], 0)
        seen[sp[0]] = n + 1
        if n < len(by_name.get(sp[0], ())):
            pairs.append((sp, by_name[sp[0]][n]))
    return pairs


def clock_error_ns(mine: list, theirs: list) -> tuple[int, int, int]:
    """``(pairs, offset, worst)``: spans matched, the offset of the first
    pair, and the largest start or end difference after that offset."""
    pairs = match_spans(mine, theirs)
    if not pairs:
        return 0, 0, 0
    off = pairs[0][1][1] - pairs[0][0][1]
    worst = max(max(abs(t[1] - m[1] - off), abs(t[2] - m[2] - off))
                for m, t in pairs)
    return len(pairs), off, worst


_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name="([^"]*)"')


def scopes_of(hlo_text: str) -> dict:
    """``{instruction name: scope}`` from a compiled program's HLO text:
    the innermost of ``SCOPES`` in the instruction's ``op_name``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_OP.match(line)
        if m:
            hit = [c for c in m.group(2).split("/") if c in SCOPES]
            if hit:
                out[m.group(1)] = hit[-1]
    return out


def scan_phases(tr: trace.Trace, modules: list,
                scopes: list) -> Optional[dict]:
    """Device seconds of the leaf ops of device 0 inside the traced
    window per named scope, ``UNSCOPED`` for the rest.  ``scopes[k]``
    maps the op names of the k-th program execution of the window (op
    names repeat across programs) to their scope; ``None`` when the
    executions and the maps do not pair up."""
    reqs = [(s, e) for n, s, e in tr.annotations if n == trace.REQUEST]
    if not reqs:
        return None
    lo, hi = min(s for s, _ in reqs), max(e for _, e in reqs)
    mods = sorted((s, e) for d, _, s, e in modules
                  if d == 0 and lo <= s and e <= hi)
    if not mods or len(mods) != len(scopes):
        return None
    ops = sorted((s, e, n) for d, n, s, e in tr.ops
                 if d == 0 and not n.startswith(NOT_LEAF))
    out: dict = {}
    k = 0
    for s, e, n in ops:
        while k < len(mods) and mods[k][1] <= s:
            k += 1
        if k == len(mods) or s < mods[k][0]:
            continue
        name = scopes[k].get(n, UNSCOPED)
        out[name] = out.get(name, 0.0) + (e - s) * 1e-9
    return out


def compiled_text(program: Callable, args: tuple) -> str:
    """The optimized HLO text of a device program as compiled for
    ``args`` (the executables the trace names its ops after)."""
    from repro.compat import scoped_x64
    with scoped_x64():
        return program.lower(*args).compile().as_text()


def profile(client: Any, specs: list, log_dir: str,
            n: int = REQUESTS) -> tuple:
    """Profile ``n`` requests of ``specs`` with the program's recorder
    on, each a root span ``trace.REQUEST``.  Returns the profile's
    ``.xplane.pb`` path, the recording, and per program run its
    ``(program, arguments)``; the layer functions the benchmark wraps
    are wrapped here too, so the trace holds their annotations."""
    import jax

    from chipbench import run
    from repro.core import jax_device_loop as dl
    from repro.core import spans
    plain = {fn: getattr(dl, fn) for fn in run.SPANS}
    calls: list = []

    def capture(*a: Any, _fn: Callable = plain["_run_program"]) -> Any:
        calls.append(a)
        return _fn(*a)
    dl._run_program = capture
    run.Spans().install()
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        with spans.recording() as rec:
            for _ in range(n):
                with spans.span(trace.REQUEST):
                    client.send(specs)
        jax.profiler.stop_trace()
    finally:
        for fn, f in plain.items():
            setattr(dl, fn, f)
    return trace.find_xplane(log_dir), rec, calls


def scope_maps(calls: list, keep: Optional[set] = None) -> list:
    """Per program run, ``{op name: scope}`` of its compiled program
    (restricted to the names in ``keep``, if given)."""
    maps: dict = {}
    for a in calls:
        if id(a[0]) not in maps:
            full = scopes_of(compiled_text(a[0], a[1:]))
            maps[id(a[0])] = {n: s for n, s in full.items()
                              if keep is None or n in keep}
    return [maps[id(a[0])] for a in calls]


def shares(d: dict) -> dict:
    total = sum(d.values())
    return {k: 100.0 * v / total for k, v in sorted(
        d.items(), key=lambda x: -x[1])} if total else {}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    from chipbench import generator, run
    cell = run.load_cell(ROOT, args.workload)
    run.device_check(cell.chips)
    run.compile_cache(ROOT)
    traffic = generator.Traffic(cell.mix, cell.cfg["architectures"],
                                args.seed)
    client = run.Client(cell.cfg)
    for k in range(2):
        client.send(client.specs(traffic.request(k, generator.WARMUP)))
    log_dir = os.path.join(ROOT, ".chipbench", "program_trace")
    path, rec, calls = profile(client, client.specs(traffic.request(0)),
                               log_dir)
    tr = trace.read_xplane(path, run.SPANS)
    ptr = read_program(path)
    shutil.rmtree(log_dir, ignore_errors=True)
    holes = window_holes(tr)
    by_span = idle_by_span(holes, ptr.spans)
    phases = scan_phases(tr, ptr.modules, scope_maps(calls))
    mine = [(s.name, s.start_ns, s.end_ns) for s in rec.spans
            if s.name.startswith(SPAN_PREFIX)]
    n, off, worst = clock_error_ns(mine, ptr.spans)
    print(f"{args.workload}: clock: {n} of {len(mine)} recorded spans "
          f"matched their annotations; offset {off} ns; largest start or "
          f"end difference after it {worst * 1e-3!r} us")
    print(f"idle s {sum(e - s for s, e in holes) * 1e-9!r}; idle_by_span "
          f"s {by_span!r}; % {shares(by_span)!r}")
    print(f"scan_phases s {phases!r}; % {shares(phases or {})!r}")


if __name__ == "__main__":
    main()
