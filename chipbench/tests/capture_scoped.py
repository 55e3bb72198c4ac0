"""Capture the scoped-trace fixture on a TPU.

    python3 chipbench/tests/capture_scoped.py <out-dir>

Profiles two requests of a tiny work-sharing cell (MSS, 2 x 2 clients,
32 messages, 2 seed lanes) with the benchmark's layer wrappers and the
program's own recorder on (``chipbench.program_trace.profile``), and
writes:

* ``scoped.xplane.pb.gz``: the profiler trace;
* ``scoped.json``: the recorder's spans (``[name, start_ns, end_ns,
  parent, request]``), its counters, and per program run the named
  scope of each op name the trace holds.

``chipbench/tests/data`` holds the pair the tests read.
"""

from __future__ import annotations

import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), HERE]


def capture(out: str, archs: tuple = ("mss",)) -> None:
    import shutil

    from _tiny import tiny_config
    from chipbench import program_trace as pt
    from chipbench import run, trace
    cfg = tiny_config()
    cfg.update(n_producers=2, n_consumers=2, total_messages=32)
    client = run.Client(cfg)
    specs = client.specs([(a, [11, 12]) for a in archs])
    client.send(specs)                          # compiles
    log_dir = os.path.join(out, ".trace")
    src, rec, calls = pt.profile(client, specs, log_dir)
    dst = os.path.join(out, "scoped.xplane.pb.gz")
    with open(src, "rb") as f, gzip.open(dst, "wb") as g:
        g.write(f.read())
    shutil.rmtree(log_dir, ignore_errors=True)
    tr = trace.read_xplane(dst, run.SPANS)
    ptr = pt.read_program(dst)
    maps = pt.scope_maps(calls, keep={n for _, n, _, _ in tr.ops})
    with open(os.path.join(out, "scoped.json"), "w") as f:
        json.dump({"spans": [list(s) for s in rec.spans],
                   "counters": rec.counters, "scopes": maps}, f,
                  separators=(",", ":"))
    mine = [(s.name, s.start_ns, s.end_ns) for s in rec.spans
            if s.name.startswith(pt.SPAN_PREFIX)]
    holes = pt.window_holes(tr)
    print(os.path.getsize(dst), "bytes;", len(tr.ops), "ops;",
          len(ptr.modules), "modules;", len(calls), "program runs;",
          "clock (pairs, offset, worst ns)", pt.clock_error_ns(mine, ptr.spans),
          "idle", sum(e - s for s, e in holes) * 1e-9,
          "by span", pt.idle_by_span(holes, ptr.spans),
          "phases", pt.scan_phases(tr, ptr.modules, maps), flush=True)


def main() -> None:
    os.makedirs(sys.argv[1], exist_ok=True)
    capture(sys.argv[1])


if __name__ == "__main__":
    main()
